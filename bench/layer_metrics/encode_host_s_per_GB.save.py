"""Seconds of the codec's encode stage per GB of state saved: the
checkpointer's ``stats["encode_s"]`` (host wall time around
``ECCodec.encode_many``, which ends when the parity is back on the host)
over the window's saved bytes."""


def read(obs):
    gb = obs["counters"].get("bytes_saved", 0) / 1e9
    if gb <= 0:
        return None
    return obs["counters"]["ckpt.encode_s"] / gb
