"""BENCHMARK.json is well formed, and every piece it names loads by name."""

import json
import os
import re

import pytest

from yardstick_tiny import BENCH, ROOT, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNITS = {"MB/s", "s", "%", "s/GB"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert spec["command"] == ["python3", "bench/run.py"]
    assert 1 <= spec["run_seconds"] <= 51


def test_names_units_and_keys(spec):
    allowed = {"configs": {"name", "source", "file", "reduced", "why"},
               "workloads": {"name", "config", "traffic", "chips", "why"},
               "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
               "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"}}
    for section, keys in allowed.items():
        names = [e["name"] for e in spec[section]]
        assert len(names) == len(set(names))
        for e in spec[section]:
            assert set(e) <= keys, (section, e)
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert e["unit"] in UNITS
                assert e["better"] in ("lower", "higher")
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and not re.search(r"[\n\t]", e[key])


def test_every_piece_loads_by_name(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        data = run.load_json(ROOT, c["file"])
        assert data["name"] == c["name"] and data["source"] == c["source"]
    for cell in spec["workloads"]:
        assert cell["config"] in configs
        mix = run.load_json(BENCH, "traffic", f"{cell['traffic']}.json")
        assert hasattr(run.load_kind(mix["kind"]), "Runner")
        assert cell["chips"] == 1
    for m in spec["per_layer"]:
        path = os.path.join(BENCH, "layer_metrics", f"{m['name']}.py")
        assert os.path.exists(path), path


@pytest.mark.parametrize("cell", ["ckpt_save.chameleon"])
def test_cell_reports_setup_another_e2e_and_a_layer_metric(spec, cell):
    e2e = {m["name"] for m in run.end_to_end_of(spec, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = run.per_layer_of(spec, cell)
    assert layer
    for m in layer:
        assert m["moves"] in e2e


def test_reduced_keys_are_the_files_cuts(spec):
    """Every cut from the source is listed in ``reduced``, stands in the
    configuration's file with the source's value beside it, and is no
    width."""
    width = re.compile(r"^(hidden_size|intermediate_size|head_dim)$|_dim$|_rank$|per_tok")
    for c in spec["configs"]:
        data = run.load_json(ROOT, c["file"])
        assert c["reduced"] == data["reduced"]
        assert set(data["reduced"]) == set(data["source_values"]) == set(data["cuts"])
        for key in c["reduced"]:
            assert NAME.match(key) and not width.search(key), key
            assert data[key] != data["source_values"][key]


def test_setup_bound(spec):
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
