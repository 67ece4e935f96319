"""Seconds of cutting the saved leaves into group payloads per GB of
state saved: the ``ckpt.split`` spans (``tobytes``, the group slices and
the bucket padding, one per leaf) of the window's saves."""

import save_spans


def read(obs):
    return save_spans.seconds_per_gb(obs, ("ckpt.split",))
