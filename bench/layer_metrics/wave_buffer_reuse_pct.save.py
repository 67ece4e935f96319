"""Share of the wave buffers the window's saves took from the
checkpointer's free list instead of allocating, in %: one less the
``codec.wave_alloc`` spans over the ``codec.wave_buffer`` spans (one per
encode wave).  Nothing to read where the program has no wave buffers."""

import save_spans


def read(obs):
    totals = save_spans.window(obs)
    if totals is None or "codec.wave_buffer" not in totals:
        return None
    allocs = totals.get("codec.wave_alloc", {"count": 0})["count"]
    return 100.0 * (1 - allocs / totals["codec.wave_buffer"]["count"])
