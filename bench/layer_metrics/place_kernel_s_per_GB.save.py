"""Seconds of the D-Rex SC decision kernel per GB of state saved: the
``place.kernel`` spans (a launch of ``score_windows_batch`` on the host
CPU device and its wait) of the window's saves."""

import place_window


def read(obs):
    return place_window.seconds_per_gb(obs, "place.kernel")
