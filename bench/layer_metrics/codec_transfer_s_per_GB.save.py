"""Seconds of the codec's host-device transfers per GB of state saved:
the ``codec.h2d`` (a wave's rows to the device and the kernel's
dispatch) and ``codec.d2h`` (parity back to the host) spans of the
window's saves."""

import save_spans


def read(obs):
    return save_spans.seconds_per_gb(obs, ("codec.h2d", "codec.d2h"))
