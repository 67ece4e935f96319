"""Seconds of placement per GB of state saved: the checkpointer's
``stats["place_s"]`` (the summed ``overhead_s`` of its ``place_many``
records) over the window's saved bytes."""


def read(obs):
    gb = obs["counters"].get("bytes_saved", 0) / 1e9
    if gb <= 0:
        return None
    return obs["counters"]["ckpt.place_s"] / gb
