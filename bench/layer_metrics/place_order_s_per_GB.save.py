"""Seconds spent readying the decision kernel's inputs per GB of state
saved: the ``place.order`` spans (free-space order and its tracker, the
pre-filter slice, per-item failure probabilities and saturation sums)
of the window's saves."""

import place_window


def read(obs):
    return place_window.seconds_per_gb(obs, "place.order")
