"""Seconds the save's own thread waits on the fabric puts per GB of state
saved: the ``ckpt.put_wait`` spans (the double buffer's wait and the
final drain) of the window's saves."""

import save_spans


def read(obs):
    return save_spans.seconds_per_gb(obs, ("ckpt.put_wait",))
