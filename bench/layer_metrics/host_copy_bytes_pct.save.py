"""Bytes the save path writes into new host buffers, in % of the state's
bytes saved: the summed ``nbytes`` of the ``ckpt.d2h``, ``ckpt.split``,
``codec.stage``, ``codec.concat``, ``codec.d2h``, ``codec.assemble`` and
``ckpt.put`` spans of the window's saves, each reckoned from the shapes
where the copy is made."""

import save_spans

HOST_COPIES = ("ckpt.d2h", "ckpt.split", "codec.stage", "codec.concat", "codec.d2h",
               "codec.assemble", "ckpt.put")


def read(obs):
    totals = save_spans.window(obs)
    if totals is None:
        return None
    copied = sum(totals[n]["nbytes"] for n in HOST_COPIES if n in totals)
    return 100.0 * copied / obs["counters"]["bytes_saved"]
