"""The 12,000-drive Backblaze-vault fleet, ``b2vault12k``, and its cell
``ckpt_save.b2vault12k``: the configuration's rows are the paper's Most
Used drive set laid out as ten vaults, the cell reports the save metrics
and the placement engine's, the readers of the placement spans and
counters read a window, and a toy run of the cell is correct."""

import json
import os

import numpy as np
import pytest

import yardstick_tiny
from yardstick_tiny import BENCH, ROOT, run

from repro import telemetry  # noqa: E402
from repro.storage.nodesets import NODE_SETS  # noqa: E402

CELL = "ckpt_save.b2vault12k"
PLACE_METRICS = ("place_kernel_s_per_GB.save", "place_order_s_per_GB.save",
                 "place_repeat_pct.save")
#: the save pipeline's and codec's span readers, shared with chameleon
PIPELINE_METRICS = ("ckpt_d2h_s_per_GB.save", "ckpt_split_s_per_GB.save",
                    "ckpt_put_wait_s_per_GB.save", "codec_copy_s_per_GB.save",
                    "codec_transfer_s_per_GB.save", "host_copy_bytes_pct.save",
                    "wave_buffer_reuse_pct.save")
PLACE_SPANS = ("place.order", "place.kernel", "place.select")
PLACE_COUNTERS = ("place.rows", "place.distinct")
SAVED = 1000  # user bytes of one synthetic save


def _config(name):
    return run.load_json(BENCH, "configs", f"{name}.json")


def test_rows_are_ten_vaults_of_the_most_used_drives():
    rows = _config("b2vault12k")["cluster"]["rows"]
    want = [[f"{model}-v{v}-p{p}-d{d}", cap, w, r, afr]
            for v, (model, cap, w, r, afr) in enumerate(NODE_SETS["most_used"])
            for p in range(20) for d in range(60)]
    assert len(rows) == 10 * 20 * 60 == 12_000
    assert rows == want


def test_only_the_cluster_and_the_depth_differ_from_chameleon():
    new, old = _config("b2vault12k"), _config("chameleon")
    differ = {k for k in set(new) | set(old) if new.get(k) != old.get(k)}
    assert differ == {"name", "source", "deployment", "cluster", "assumed"}
    # the same state: 3 of Yi-6B's 32 layers, no embedding or head
    assert new["cuts"] == old["cuts"]
    assert (new["num_hidden_layers"], new["vocab_size"]) == (3, 0)
    assert new["cluster"]["kind"] == "nodes"
    assert new["cluster"]["columns"] == old["cluster"]["columns"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [c for c in spec["configs"] if c["name"] == "b2vault12k"]
    assert entry["source"] == new["source"] and len(entry["source"]) <= 200


def test_cell_reports_the_save_and_placement_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (cell,) = [c for c in spec["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("b2vault12k", "ckpt_save", 1)
    assert {m["name"] for m in run.end_to_end_of(spec, CELL)} == {"setup_s", "save_MBps"}
    layer = {m["name"]: m for m in run.per_layer_of(spec, CELL)}
    assert {"rs_hbm_roofline.encode", "place_s_per_GB.save",
            "encode_host_s_per_GB.save", *PIPELINE_METRICS, *PLACE_METRICS} == set(layer)
    for name in PLACE_METRICS:
        assert layer[name]["layer"] == "placement engine"
        assert layer[name]["moves"] == "save_MBps"
        assert layer[name]["workloads"] == ["ckpt_save.chameleon", CELL]


# -- the readers, on synthetic windows ------------------------------------------

def _save(step: int, place: bool, scale: int) -> None:
    """One synthetic save; ``place`` adds the placement spans and counters
    (two kernel launches of 4 and 2 rows, 3 and 1 of them distinct)."""
    with telemetry.span("ckpt.save", SAVED, request=step):
        with telemetry.span("ckpt.place"):
            if place:
                for rows, distinct in ((4, 3), (2, 1)):
                    for name in PLACE_SPANS:
                        with telemetry.span(name):
                            pass
                    telemetry.count("place.rows", rows * scale)
                    telemetry.count("place.distinct", distinct * scale)


@pytest.fixture
def window():
    """Two warm saves with other counts, then a window of three."""
    telemetry.reset(prefilter_counters=False, matrix_caches=False, compile_census=False)

    def make(place=True):
        for step in range(5):
            _save(step, place, 7 if step < 2 else 1)
        return {"counters": {"bytes_saved": 3 * SAVED}}

    yield make
    telemetry.reset(prefilter_counters=False, matrix_caches=False, compile_census=False)


@pytest.mark.parametrize("name,span", [("place_kernel_s_per_GB.save", "place.kernel"),
                                       ("place_order_s_per_GB.save", "place.order")])
def test_span_readers_read_the_window(window, name, span):
    obs = window()
    per = telemetry.span_stats()["requests"][2:]
    want = sum(r["spans"][span]["seconds"] for r in per) / (3 * SAVED / 1e9)
    assert run.read_layer_metric(name, obs) == pytest.approx(want, rel=1e-9)


def test_repeat_share_reads_the_window(window):
    # 6 rows a save, 4 of them distinct, in each of the three saves
    assert run.read_layer_metric("place_repeat_pct.save", window()) == pytest.approx(100 / 3)


@pytest.mark.parametrize("name", PLACE_METRICS)
def test_nothing_to_read(window, monkeypatch, name):
    # the program before the placement spans and counters
    assert run.read_layer_metric(name, window(place=False)) is None
    obs = window()
    assert run.read_layer_metric(name, {"counters": {"bytes_saved": 0}}) is None
    # the newest saves do not add up to the window
    assert run.read_layer_metric(name, {"counters": {"bytes_saved": 2500}}) is None
    monkeypatch.delattr(telemetry, "span_stats")
    assert run.read_layer_metric(name, obs) is None


# -- the cell at a toy size --------------------------------------------------------

@pytest.fixture(scope="module")
def traced():
    """A traced toy run of the cell, and its window's placement totals."""
    with pytest.MonkeyPatch.context() as mp:
        yardstick_tiny.tiny_loader(mp)
        out = run.run_cell(yardstick_tiny.args(CELL, trace=1), require_tpu=False)
        import place_window
        import save_spans

        obs = {"counters": out["info"]["counters"]}
        yield out, save_spans.window(obs), place_window.counters(obs)


def test_toy_run_is_correct(traced):
    out, _, _ = traced
    assert out["correct"], out["checks"]
    assert out["checks"]["decisions_off"]["value"] == 0
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_toy_window_holds_the_placement_spans_and_counters(traced):
    out, spans, counts = traced
    saves = spans["ckpt.save"]["count"]
    assert saves == out["attempted"]
    for name in PLACE_SPANS:
        assert spans[name]["count"] >= saves, name
    assert spans["place.kernel"]["seconds"] <= spans["ckpt.place"]["seconds"]
    # the toy state has 27 groups, placed in one launch a save
    assert set(PLACE_COUNTERS) == set(counts)
    assert counts["place.rows"] == 27 * saves
    assert 0 < counts["place.distinct"] < counts["place.rows"]
    # (the coding kernel's roofline share needs the TPU's Pallas kernel)
    for name in PLACE_METRICS + ("place_s_per_GB.save",):
        assert out["metrics"][name]["value"] > 0, name
    # the save pipeline's and codec's readers read this cell as chameleon
    for name in PIPELINE_METRICS:
        assert out["metrics"][name]["value"] >= 0, name


def test_empty_fleet_slice_is_one_vault():
    """On the empty fleet the kernel sees only the pre-filter's first
    1,096 drives of the free-space order: all of them in vault 3, the
    first 16 TB vault, so one drive model."""
    import generate
    import harness
    from repro.core.types import ClusterView, DataItem

    config = _config("b2vault12k")
    a = generate.cluster_arrays(config["cluster"])
    view = ClusterView(a["capacity_mb"], a["used_mb"], a["write_bw"], a["read_bw"],
                       a["afr"], np.ones(len(a["afr"]), dtype=bool))
    sched = harness.make_scheduler(config["scheduler"])
    items = [DataItem(i, mb, 0.0, 365.0, 0.99999) for i, mb in enumerate((0.004096, 32.0))]
    by_free_k, candidates, _ = sched._kernel_inputs(items, [0.004096] * 2, view, None)
    assert candidates == 12_000
    assert len(by_free_k) == 1096
    assert (by_free_k // 1200 == 3).all()
    assert {config["cluster"]["rows"][i][0].split("-v")[0] for i in by_free_k} == \
        {"ST16000NM001G"}


def test_toy_control_is_not_correct(monkeypatch):
    out = yardstick_tiny.run_tiny(monkeypatch, CELL, control=True)
    assert not out["correct"]
    assert out["checks"]["parity_bytes_off"]["value"] > 0
