"""Seconds of the codec's host copies per GB of state saved: the
``codec.stage`` (payloads into (K, B) rows), ``codec.concat`` (a wave's
rows side by side) and ``codec.assemble`` (data and parity rows into
chunks) spans of the window's saves."""

import save_spans


def read(obs):
    return save_spans.seconds_per_gb(obs, ("codec.stage", "codec.concat", "codec.assemble"))
