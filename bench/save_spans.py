"""The program's span totals (``repro.telemetry``) over a window's saves.

Every span of a checkpoint save carries the save's step as its request,
and the telemetry keeps per-request totals for the latest requests.  The
window's saves are the last ones the process ran (nothing saves after the
window), so the window's totals are those of the newest requests whose
``ckpt.save`` bytes add up to the window's ``bytes_saved``.  Nothing to
read (``None``) where the program keeps no spans, where nothing was
saved, or where the newest requests do not add up to the window.
"""

from __future__ import annotations


def window(obs) -> dict | None:
    """``{span name: {count, seconds, self_seconds, nbytes}}`` summed over
    the window's saves."""
    from repro import telemetry

    saved = obs["counters"].get("bytes_saved", 0)
    stats = getattr(telemetry, "span_stats", None)
    if stats is None or saved <= 0:
        return None
    out: dict = {}
    seen = 0
    for req in reversed(stats()["requests"]):
        save = req["spans"].get("ckpt.save")
        if save is None:
            continue
        seen += save["nbytes"]
        for name, t in req["spans"].items():
            acc = out.setdefault(name, dict.fromkeys(t, 0))
            for key, v in t.items():
                acc[key] += v
        if seen >= saved:
            break
    return out if seen == saved else None


def seconds_per_gb(obs, names) -> float | None:
    """Summed seconds of the named spans in the window per GB saved."""
    totals = window(obs)
    if totals is None:
        return None
    secs = sum(totals[n]["seconds"] for n in names if n in totals)
    return secs / (obs["counters"]["bytes_saved"] / 1e9)
