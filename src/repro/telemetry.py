"""One facade over the library's introspection surfaces.

Five subsystems keep counters that benchmarks and the gated lanes stamp
into JSON: the top-M pre-filter (:func:`repro.core.prefilter.stats`),
the EC coefficient-matrix caches
(:func:`repro.kernels.ops.matrix_cache_stats`), the shape-bucketer
compile census (:func:`repro.core.shapes.compile_cache_stats`), the
per-engine :class:`~repro.core.engine.PlacementEngine` decision counters
(``engine.stats``), and the persistent XLA compilation cache
(:func:`repro.core.jitcache.status`).  Importing each module ad hoc
couples every benchmark to four internal layouts; this facade freezes one stable
schema (:class:`TelemetrySnapshot`) behind :func:`snapshot` /
:func:`reset`.

The leaf dictionaries are byte-compatible with what the underlying
surfaces emit (the facade copies, it does not reshape), so benchmark
JSON stamped through ``snapshot()`` is identical to what the ad-hoc
imports produced — no baseline churn.

The first three surfaces are process-wide; engine counters live on each
:class:`PlacementEngine` instance, so ``snapshot(engine=...)`` takes the
instance to read (``engine=None`` in the snapshot otherwise), and
:func:`reset` only touches the process-wide state.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

__all__ = ["TelemetrySnapshot", "snapshot", "reset"]


@dataclasses.dataclass(frozen=True)
class TelemetrySnapshot:
    """Point-in-time copy of every introspection surface (safe to
    mutate; the live counters are not aliased)."""

    #: per-scheduler pre-filter events (engaged / accepted / fallback /
    #: bypassed / promoted) — ``repro.core.prefilter.stats()``.
    prefilter: dict[str, dict[str, int]]
    #: EC coefficient-matrix builds and LRU hit rates —
    #: ``repro.kernels.ops.matrix_cache_stats()``.
    matrix_cache: dict[str, Any]
    #: jit compile census per kernel family —
    #: ``repro.core.shapes.compile_cache_stats()``.
    compile_cache: dict[str, Any]
    #: decision counters of the engine passed to :func:`snapshot`
    #: (placements, rejections, constraint swaps, repair gauges), or
    #: ``None`` when no engine was given.
    engine: Optional[dict[str, Any]] = None
    #: persistent XLA compilation-cache state —
    #: ``repro.core.jitcache.status()``.
    jit_cache: Optional[dict[str, Any]] = None

    def as_dict(self) -> dict[str, Any]:
        """Plain-dict view for JSON stamping."""
        return dataclasses.asdict(self)


def snapshot(engine=None) -> TelemetrySnapshot:
    """Copy every introspection surface; pass a
    :class:`~repro.core.engine.PlacementEngine` to include its
    per-instance decision counters."""
    from repro.core import jitcache, prefilter, shapes
    from repro.kernels import ops as kops

    return TelemetrySnapshot(
        prefilter=prefilter.stats(),
        matrix_cache=kops.matrix_cache_stats(),
        compile_cache=shapes.compile_cache_stats(),
        engine=dict(engine.stats) if engine is not None else None,
        jit_cache=jitcache.status(),
    )


def reset(
    *,
    prefilter_counters: bool = True,
    matrix_caches: bool = True,
    compile_census: bool = True,
) -> None:
    """Zero the process-wide counters (benchmark lane isolation).

    Engine counters are per-instance and unaffected — construct a fresh
    engine instead.  Resetting the compile census clears the bucketer's
    issued-shape census, not the jit caches themselves.
    """
    from repro.core import prefilter, shapes
    from repro.kernels import ops as kops

    if prefilter_counters:
        prefilter.reset_stats()
    if matrix_caches:
        kops.reset_matrix_caches()
    if compile_census:
        shapes.reset()
