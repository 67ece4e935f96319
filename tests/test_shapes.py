"""Shape-bucketing + compile-cache regression tests (core/shapes.py).

The jitted kernels recompile once per static shape signature; the
shared bucketer must (a) keep pads masked-safe (always >= the request),
(b) bound the number of distinct shapes an elastic cluster can generate
(geometric rungs), and (c) absorb join/heal oscillation around a rung
boundary (hysteresis band).  The churn-budget tests are the regression
teeth for the ROADMAP's "per-shape-bucket recompiles on elastic
clusters" item: a simulated join/heal sequence must stay within a fixed
compile budget, asserted through the compile-cache counter the kernels
feed (`shapes.record_compile` / `compile_cache_stats`).
"""

import numpy as np
import pytest

from repro.core import shapes
from repro.core.shapes import ShapeBucketer, rung
from repro.core import (
    ClusterView,
    DataItem,
    PlacementEngine,
    StorageNode,
    create_scheduler,
)


class TestRungLadder:
    def test_covers_and_aligns(self):
        for n in range(1, 700):
            r = rung(n)
            assert r >= n
            assert r % shapes.ALIGN == 0

    def test_exact_multiples_below_geometric_regime(self):
        # Small shapes keep the historical round-up-to-8 ladder.
        for n in range(1, shapes.GEOMETRIC_FROM + 1):
            assert rung(n) == max(8, ((n + 7) // 8) * 8)

    def test_geometric_above(self):
        # Rung count from 64 to 10k grows logarithmically: a cluster
        # scaling 100 -> 10000 one join at a time compiles O(log) times.
        rungs = {rung(n) for n in range(65, 10_000)}
        assert len(rungs) < 25
        ladder = sorted(rungs)
        ratios = [b / a for a, b in zip(ladder, ladder[1:])]
        assert max(ratios) <= shapes.GROWTH * 1.2

    def test_monotone(self):
        vals = [rung(n) for n in range(1, 2000)]
        assert all(b >= a for a, b in zip(vals, vals[1:]))


class TestHysteresisBand:
    def test_oscillation_around_a_rung_boundary_holds_one_shape(self):
        b = ShapeBucketer()
        lo, hi = 100, 110  # straddles the 104/136 rung boundary
        pads = {b.bucket("nodes", n) for _ in range(5) for n in range(lo, hi)}
        pads |= {b.bucket("nodes", n) for _ in range(5) for n in range(hi, lo, -1)}
        assert len(pads) <= 2  # one grow step, then held
        assert b.band_hits > 0

    def test_shrink_beyond_band_releases_the_held_pad(self):
        b = ShapeBucketer()
        big = b.bucket("nodes", 500)
        small = b.bucket("nodes", 24)  # far below big / SHRINK_BAND
        assert small == rung(24) < big

    def test_shrink_within_band_keeps_the_held_pad(self):
        b = ShapeBucketer()
        held = b.bucket("nodes", 130)
        assert b.bucket("nodes", 100) == held  # rung(100)*2 >= held

    def test_kinds_are_independent(self):
        b = ShapeBucketer()
        assert b.bucket("nodes", 500) >= 500
        assert b.bucket("sc_starts", 12) == rung(12)

    def test_pad_always_covers_request(self):
        b = ShapeBucketer()
        rng = np.random.default_rng(0)
        for n in rng.integers(1, 900, size=300):
            assert b.bucket("nodes", int(n)) >= n


class TestCompileCensus:
    def test_record_compile_dedups(self):
        b = ShapeBucketer()
        assert b.record_compile("k", (8, 16))
        assert not b.record_compile("k", (8, 16))
        assert b.record_compile("k", (8, 24))
        stats = b.stats()
        assert stats["kernels"]["k"] == {"compiles": 2, "calls": 3}

    def test_default_stats_shape(self):
        stats = shapes.compile_cache_stats()
        assert set(stats) == {"queries", "band_hits", "kernels"}


def churn_cluster(n: int, seed: int = 0) -> ClusterView:
    rng = np.random.default_rng(seed)
    return ClusterView.from_nodes(
        [
            StorageNode(
                node_id=i,
                capacity_mb=float(rng.uniform(2e3, 1e5)),
                write_bw=float(rng.uniform(50, 400)),
                read_bw=float(rng.uniform(50, 450)),
                annual_failure_rate=float(rng.uniform(0.001, 0.05)),
            )
            for i in range(n)
        ]
    )


class TestRecompileBudgetUnderChurn:
    """A node_join/node_heal churn sequence must stay within the bucket
    budget — the compile census counts every distinct static signature
    the kernel would compile."""

    def test_lb_kernel_join_heal_churn(self):
        # 90 -> 110 -> 95 one node at a time (joins, then fail/heals),
        # crossing the old round-up-to-8 ladder 4 times; the banded
        # buckets must hold this to <= 2 node shapes (one per batch pad
        # actually used).
        sched = create_scheduler("drex_lb")
        sched.KERNEL_MIN_NODES = 0
        sched.KERNEL_MIN_NODES_BATCH = 0
        item = DataItem(0, 50.0, 0.0, 365.0, 0.99)
        before = shapes.issued_shapes("lb_kernel")
        sizes = list(range(90, 111)) + list(range(110, 94, -1))
        for n in sizes:
            sched.place_batch([item], churn_cluster(n), None)
        new = shapes.issued_shapes("lb_kernel") - before
        node_pads = {sig[1] for sig in new}
        assert len(node_pads) <= 2, f"churn issued node pads {node_pads}"

    def test_bucketer_budget_is_logarithmic_under_wide_churn(self):
        # Pure-bucketer variant (no jit cost): a 2x elastic range maps
        # onto at most 4 pads.
        b = ShapeBucketer()
        rng = np.random.default_rng(7)
        pads = {b.bucket("nodes", int(n)) for n in rng.integers(250, 500, 400)}
        assert len(pads) <= 4

    def test_decisions_invariant_to_bucket_history(self):
        # The same cluster placed through a fresh bucketer state and a
        # held-oversized one must decide identically (pads are masked).
        sched = create_scheduler("drex_lb")
        sched.KERNEL_MIN_NODES = 0
        item = DataItem(0, 50.0, 0.0, 365.0, 0.99)
        cluster = churn_cluster(100)
        want = sched.place(item, cluster)
        # Inflate the held node pad far beyond 100, within the band.
        shapes.DEFAULT.bucket("nodes", 130)
        got = sched.place(item, cluster)
        assert got.placement == want.placement
        assert got.candidates_considered == want.candidates_considered


class TestKernelsEngaged:
    """The decision kernels are a hard dependency: a batched placement
    under the default dispatch policy must reach every kernel module.
    An import or dispatch break then fails here instead of silently
    sending every decision to the scalar oracle."""

    #: scheduler -> census name of the kernel its batches must reach
    #: (both greedy schedulers live in ``repro.core.greedy_kernel``).
    KERNELS = {
        "drex_sc": "sc_kernel",
        "drex_lb": "lb_kernel",
        "greedy_min_storage": "min_storage_kernel",
        "greedy_least_used": "least_used_kernel",
    }

    @pytest.mark.parametrize("name", sorted(KERNELS))
    def test_place_many_records_kernel_signature(self, name):
        kernel = self.KERNELS[name]
        # 200 live nodes clear every batch crossover (GreedyLeastUsed's
        # is the largest, KERNEL_MIN_NODES_BATCH = 192).
        engine = PlacementEngine(churn_cluster(200), name, auto_commit=False)
        items = [DataItem(i, 50.0 + i, float(i), 365.0, 0.999) for i in range(8)]

        def calls():
            return shapes.compile_cache_stats()["kernels"].get(
                kernel, {"calls": 0}
            )["calls"]

        before = calls()
        records = engine.place_many(items)
        assert all(r.ok for r in records)
        assert calls() > before
        assert shapes.issued_shapes(kernel)
        devices = shapes.kernel_devices()[kernel]
        assert devices and all(
            d.startswith(shapes.DECISION_PLATFORM + ":") for d in devices
        )


class TestDecisionScope:
    """The one placement choice for the float64 decision kernels."""

    def test_scope_sets_x64_and_decision_device(self):
        import jax

        with shapes.decision_scope():
            assert jax.config.jax_enable_x64
            x = jax.numpy.zeros(3)
        assert x.dtype == np.float64
        assert {d.platform for d in x.devices()} == {shapes.DECISION_PLATFORM}
        assert not jax.config.jax_enable_x64

    def test_record_device_names_platform_and_id(self):
        import jax

        b = ShapeBucketer()
        b.record_device("k", jax.numpy.zeros(2))
        dev = jax.devices()[0]
        assert b.kernel_devices() == {"k": (f"{dev.platform}:{dev.id}",)}
        b.reset()
        assert b.kernel_devices() == {}


class TestCompilationCacheDir:
    """The persistent compile cache lives where the environment says,
    else at one fixed path inside the checkout — never relative to the
    working directory."""

    def test_checkout_dir_ignores_cwd(self, monkeypatch, tmp_path):
        import pathlib

        from repro.core import jitcache

        monkeypatch.delenv(jitcache.ENV_DIR, raising=False)
        monkeypatch.chdir(tmp_path)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert jitcache.cache_dir() == root / "results" / ".jax_cache"
        assert jitcache.status()["source"] == "checkout"

    def test_env_dir_wins(self, monkeypatch, tmp_path):
        from repro.core import jitcache

        monkeypatch.setenv(jitcache.ENV_DIR, str(tmp_path))
        assert jitcache.cache_dir() == tmp_path
        assert jitcache.status()["source"] == "env"

    def test_cache_is_configured(self):
        import jax

        from repro.core import jitcache

        assert jax.config.jax_compilation_cache_dir == str(jitcache.cache_dir())
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
