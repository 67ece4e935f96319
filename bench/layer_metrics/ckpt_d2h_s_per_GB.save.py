"""Seconds of the save's device-to-host copies per GB of state saved:
the ``ckpt.d2h`` spans (``np.asarray(jax.device_get(leaf))``, one per
leaf) of the window's saves over their bytes."""

import save_spans


def read(obs):
    return save_spans.seconds_per_gb(obs, ("ckpt.d2h",))
