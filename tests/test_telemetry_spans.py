"""Host spans of ``repro.telemetry``: nesting and self time, request ids,
exact totals under threads, reset, listeners, counters per request, the
snapshot schema, and the spans of a checkpoint save (every stage fires,
the save's own thread is covered, and the bytes match what the manifest
says was copied)."""

import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.checkpoint import CheckpointPolicy, DRexCheckpointer, StorageFabric
from repro.storage import make_node_set

N_THREADS = 8
N_PER_THREAD = 500


@pytest.fixture(autouse=True)
def clean_spans():
    telemetry.reset(prefilter_counters=False, matrix_caches=False, compile_census=False)
    yield
    telemetry.reset(prefilter_counters=False, matrix_caches=False, compile_census=False)


@pytest.fixture
def records():
    got = []
    telemetry.add_span_listener(got.append)
    yield got
    telemetry.remove_span_listener(got.append)


class TestSpan:
    def test_nesting_self_time_parent_and_request(self, records):
        with telemetry.span("outer", 10, request="r1") as outer:
            time.sleep(0.01)
            with telemetry.span("inner", 5) as inner:
                time.sleep(0.02)
                inner.nbytes += 1
        assert outer.seconds >= inner.seconds >= 0.02
        by = {r.name: r for r in records}
        assert by["inner"].parent_id == by["outer"].span_id
        assert by["outer"].parent_id is None
        assert by["inner"].request == by["outer"].request == "r1"
        assert by["inner"].nbytes == 6
        assert by["inner"].thread == by["outer"].thread == threading.get_ident()
        totals = telemetry.span_stats()["totals"]
        assert totals["inner"]["self_seconds"] == totals["inner"]["seconds"]
        assert totals["outer"]["self_seconds"] == pytest.approx(
            totals["outer"]["seconds"] - totals["inner"]["seconds"], abs=1e-9)
        assert totals["outer"]["self_seconds"] >= 0.01
        assert [r["request"] for r in telemetry.span_stats()["requests"]] == ["r1"]

    def test_span_on_another_thread_has_no_parent(self, records):
        def work():
            with telemetry.span("pool", request=7):
                pass

        with telemetry.span("main", request=7):
            th = threading.Thread(target=work)
            th.start()
            th.join(timeout=10)
        assert not th.is_alive()
        by = {r.name: r for r in records}
        assert by["pool"].parent_id is None and by["pool"].request == 7
        assert by["pool"].thread != by["main"].thread
        per = telemetry.span_stats()["requests"][0]
        assert per["request"] == 7 and set(per["spans"]) == {"pool", "main"}

    def test_an_exception_still_closes_the_span(self):
        with pytest.raises(ValueError):
            with telemetry.span("fails", 3):
                raise ValueError("boom")
        assert telemetry.span_stats()["totals"]["fails"]["count"] == 1
        with telemetry.span("after"):
            pass
        # the failed span left the thread's stack: "after" has no parent
        assert telemetry.span_stats()["totals"]["after"]["self_seconds"] == \
            telemetry.span_stats()["totals"]["after"]["seconds"]

    def test_totals_exact_under_threads(self):
        barrier = threading.Barrier(N_THREADS)

        def work(t):
            barrier.wait()
            for _ in range(N_PER_THREAD):
                with telemetry.span("hot", 3, request=t % 2):
                    with telemetry.span("hot.child", 1):
                        pass

        threads = [threading.Thread(target=work, args=(t,)) for t in range(N_THREADS)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)  # switch threads often, so a lost update shows
        try:
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(th.is_alive() for th in threads)
        stats = telemetry.span_stats()
        n = N_THREADS * N_PER_THREAD
        assert stats["totals"]["hot"]["count"] == n
        assert stats["totals"]["hot"]["nbytes"] == 3 * n
        assert stats["totals"]["hot.child"]["nbytes"] == n
        assert stats["totals"]["hot"]["self_seconds"] <= stats["totals"]["hot"]["seconds"]
        per = {r["request"]: r["spans"] for r in stats["requests"]}
        assert per[0]["hot"]["count"] == per[1]["hot"]["count"] == n // 2
        assert per[0]["hot.child"]["count"] + per[1]["hot.child"]["count"] == n

    def test_reset_and_request_bound(self):
        for r in range(telemetry.REQUESTS_KEPT + 3):
            with telemetry.span("req", request=r):
                pass
        stats = telemetry.span_stats()
        kept = [r["request"] for r in stats["requests"]]
        assert kept == list(range(3, telemetry.REQUESTS_KEPT + 3))
        assert stats["totals"]["req"]["count"] == telemetry.REQUESTS_KEPT + 3
        telemetry.reset(spans=False)
        assert telemetry.span_stats()["totals"]
        telemetry.reset(prefilter_counters=False, matrix_caches=False, compile_census=False)
        assert telemetry.span_stats() == {"totals": {}, "counters": {}, "requests": []}

    def test_listener_add_and_remove(self):
        got = []
        with telemetry.span("unheard"):
            pass
        telemetry.add_span_listener(got.append)
        with telemetry.span("heard", 2):
            pass
        telemetry.remove_span_listener(got.append)
        with telemetry.span("unheard"):
            pass
        assert [(r.name, r.nbytes) for r in got] == [("heard", 2)]
        assert got[0].end_ns >= got[0].start_ns
        # the totals count every span, listener or not
        assert telemetry.span_stats()["totals"]["unheard"]["count"] == 2

    def test_counters_nest_under_the_request_of_the_open_span(self):
        telemetry.count("rows", 3)  # no span open: process-wide only
        with telemetry.span("save", request="s1"):
            with telemetry.span("inner"):
                telemetry.count("rows", 2)
                telemetry.count("rows")
            telemetry.count("distinct", 4)
        with telemetry.span("save", request="s2"):
            telemetry.count("rows", 5)
        telemetry.count("rows", 7, request="s1")
        stats = telemetry.span_stats()
        assert stats["counters"] == {"rows": 18, "distinct": 4}
        per = {r["request"]: r for r in stats["requests"]}
        assert per["s1"]["counters"] == {"rows": 10, "distinct": 4}
        assert per["s2"]["counters"] == {"rows": 5}
        assert set(per["s1"]["spans"]) == {"save", "inner"}
        # a request that only counts is kept like one that only spans
        telemetry.count("rows", 1, request="s3")
        assert telemetry.span_stats()["requests"][-1] == \
            {"request": "s3", "spans": {}, "counters": {"rows": 1}}
        telemetry.reset(prefilter_counters=False, matrix_caches=False, compile_census=False)
        assert telemetry.span_stats()["counters"] == {}

    def test_snapshot_carries_spans(self):
        with telemetry.span("snap", 4, request=1):
            pass
        snap = telemetry.snapshot()
        assert snap.spans["totals"]["snap"] == {
            "count": 1, "seconds": snap.spans["totals"]["snap"]["seconds"],
            "self_seconds": snap.spans["totals"]["snap"]["seconds"], "nbytes": 4}
        assert snap.spans["requests"] == [
            {"request": 1, "spans": snap.spans["totals"], "counters": {}}]
        assert "spans" in snap.as_dict()


# -- the spans of a checkpoint save ----------------------------------------------

SAVE_SPANS = {"ckpt.save", "ckpt.d2h", "ckpt.split", "ckpt.place", "ckpt.encode",
              "place.order", "place.kernel", "place.select",
              "codec.wave_buffer", "codec.wave_alloc", "codec.stage", "codec.h2d",
              "codec.wait", "codec.d2h", "codec.assemble", "ckpt.put", "ckpt.put_wait"}
#: a save shaped like an earlier one reuses that one's wave buffers
WARM_SAVE_SPANS = SAVE_SPANS - {"codec.wave_alloc"}
SAVE_CHILDREN = {"ckpt.d2h", "ckpt.split", "ckpt.place", "ckpt.encode", "ckpt.put_wait"}


def _bucket(n: int) -> int:
    b = 4096
    while b < n:
        b <<= 1
    return b


def _reckoned_bytes(manifest, spans) -> dict[str, int]:
    """Bytes each span should write, from the manifest alone: leaf bytes,
    group sizes, (K, P) and the power-of-two bucket.  The wave buffer's
    acquire and allocation write nothing (``np.empty``)."""
    want = dict.fromkeys(spans, 0)
    for meta in manifest["leaves"]:
        leaf = int(np.prod(meta["shape"])) * np.dtype(meta["dtype"]).itemsize
        want["ckpt.save"] += leaf
        want["ckpt.d2h"] += leaf
        want["ckpt.split"] += leaf  # tobytes
        for g in meta["groups"]:
            n, k, p = g["orig_nbytes"], g["k"], g["p"]
            if len(meta["groups"]) > 1:
                want["ckpt.split"] += n  # a slice of part of the leaf
            b = _bucket(n)
            if b > n:
                want["ckpt.split"] += 2 * b - n  # zero filler, then padded payload
            clen = -(-b // k)
            for name, rows in (("codec.stage", k), ("codec.h2d", k), ("codec.d2h", p),
                               ("codec.assemble", p), ("ckpt.put", k + p)):
                want[name] += rows * clen
    return want


def _state():
    rng = np.random.default_rng(0)
    return {
        "split": jnp.asarray(rng.standard_normal(400_000, dtype=np.float32)),  # 7 groups
        "exact": jnp.asarray(rng.integers(0, 255, 65_536, dtype=np.uint8)),  # one bucket
        "small": jnp.asarray(rng.standard_normal(100, dtype=np.float32)),  # padded
        "mid": jnp.asarray(rng.standard_normal((64, 300), dtype=np.float32)),
    }


@pytest.mark.parametrize("workers", [0, 2])
def test_save_spans_fire_cover_and_count_bytes(records, workers):
    fabric = StorageFabric(make_node_set("most_used", capacity_scale=1e-3))
    ck = DRexCheckpointer(fabric, "drex_sc", CheckpointPolicy(
        item_mb=0.25, pipeline_workers=workers, encode_wave_groups=2))
    state = _state()
    first = ck.save(state, 0)  # compiles, allocates the wave buffers
    assert {r.name for r in records} == SAVE_SPANS
    assert telemetry.span_stats()["totals"]["codec.wave_alloc"]["nbytes"] == 0
    assert {n: t["nbytes"] for n, t in telemetry.span_stats()["totals"].items()} == \
        _reckoned_bytes(first, SAVE_SPANS)
    encode_s0 = ck.stats["encode_s"]
    del records[:]
    telemetry.reset(prefilter_counters=False, matrix_caches=False, compile_census=False)
    manifest = ck.save(state, 1)

    assert {r.name for r in records} == WARM_SAVE_SPANS
    assert {r.request for r in records} == {1}
    (save,) = [r for r in records if r.name == "ckpt.save"]
    children = [r for r in records if r.parent_id == save.span_id]
    assert {r.name for r in children} == SAVE_CHILDREN | ({"ckpt.put"} if workers == 0 else set())
    assert all(r.thread == save.thread for r in children)
    covered = sum(r.end_ns - r.start_ns for r in children)
    assert covered >= 0.9 * (save.end_ns - save.start_ns)

    got = {name: t["nbytes"] for name, t in telemetry.span_stats()["totals"].items()}
    assert got == _reckoned_bytes(manifest, WARM_SAVE_SPANS)
    assert got["ckpt.save"] == sum(x.nbytes for x in jax.tree.leaves(state))
    # the encode stage's seconds are the ckpt.encode spans'
    assert ck.stats["encode_s"] - encode_s0 == pytest.approx(
        sum(r.end_ns - r.start_ns for r in records if r.name == "ckpt.encode") / 1e9)


def test_save_counts_placement_rows_under_its_request(monkeypatch):
    """The decision kernel's counters of a save land in the save's own
    request, beside its spans: one row per group asked for, and the rows
    the kernel was launched with, which repeat no row of their launch."""
    from repro.core import sc_kernel

    launched = []
    score = sc_kernel.score_windows_batch

    def spy(probs_mat, *rest):
        launched.append(len(probs_mat))
        return score(probs_mat, *rest)

    monkeypatch.setattr(sc_kernel, "score_windows_batch", spy)
    fabric = StorageFabric(make_node_set("most_used", capacity_scale=1e-3))
    ck = DRexCheckpointer(fabric, "drex_sc", CheckpointPolicy(item_mb=0.25))
    manifest = ck.save(_state(), 5)
    groups = sum(len(meta["groups"]) for meta in manifest["leaves"])
    (req,) = telemetry.span_stats()["requests"]
    assert req["request"] == 5
    assert {"place.order", "place.kernel", "place.select"} <= set(req["spans"])
    assert req["counters"]["place.rows"] == groups
    assert req["counters"]["place.distinct"] == sum(launched)
    assert 1 <= req["counters"]["place.distinct"] < groups
    assert telemetry.span_stats()["counters"] == req["counters"]


def test_spans_reach_a_host_trace(tmp_path):
    """With host tracing on, a span is a ``TraceAnnotation`` in the trace."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with telemetry.span("ckpt.trace_probe"):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events}
    assert "ckpt.trace_probe" in names
