"""The placement engine's spans and counters (``repro.telemetry``) over a
window's saves.

The placement path opens ``place.order``, ``place.kernel`` and
``place.select`` inside each save's ``ckpt.place`` span and counts the
decision kernel's rows with ``telemetry.count``, under the save's step
as its request.  The window's saves are found as in ``save_spans``: the
newest requests whose ``ckpt.save`` bytes add up to the window's
``bytes_saved``.  Nothing to read (``None``) where the program has no
such span or counter, as before they existed.
"""

from __future__ import annotations

import save_spans


def seconds_per_gb(obs, name: str) -> float | None:
    """Seconds of span ``name`` in the window per GB saved."""
    totals = save_spans.window(obs)
    if totals is None or name not in totals:
        return None
    return totals[name]["seconds"] / (obs["counters"]["bytes_saved"] / 1e9)


def counters(obs) -> dict | None:
    """``{counter name: summed count}`` over the window's saves."""
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
        for name, n in req.get("counters", {}).items():
            out[name] = out.get(name, 0) + n
        if seen >= saved:
            break
    return out if seen == saved else None
