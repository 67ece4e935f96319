"""The reader of the codec's wave-buffer reuse: on synthetic windows,
nothing to read where the program has no wave buffers or nothing was
saved, and a traced run of the save cell at a toy size."""

import pytest

import yardstick_tiny  # puts bench/ and src/ on the path
from yardstick_tiny import run

from repro import telemetry  # noqa: E402

METRIC = "wave_buffer_reuse_pct.save"
SAVED = 1000  # user bytes of one synthetic save
WAVES = 4  # encode waves of one synthetic save


def _save(step: int, allocs: int, wave_spans: bool = True) -> None:
    """One synthetic save of ``WAVES`` waves, ``allocs`` of them into a
    freshly allocated buffer."""
    with telemetry.span("ckpt.save", SAVED, request=step):
        for w in range(WAVES):
            with telemetry.span("ckpt.encode"):
                if wave_spans:
                    with telemetry.span("codec.wave_buffer"):
                        if w < allocs:
                            with telemetry.span("codec.wave_alloc"):
                                pass
                with telemetry.span("codec.stage", 10):
                    pass


@pytest.fixture(autouse=True)
def clean_spans():
    telemetry.reset(prefilter_counters=False, matrix_caches=False, compile_census=False)
    yield
    telemetry.reset(prefilter_counters=False, matrix_caches=False, compile_census=False)


def _window(allocs_per_save, wave_spans=True):
    """Two warm saves that allocate every buffer, then the window."""
    for step in range(2):
        _save(step, WAVES, wave_spans)
    for step, allocs in enumerate(allocs_per_save, start=2):
        _save(step, allocs, wave_spans)
    return {"counters": {"bytes_saved": len(allocs_per_save) * SAVED}}


@pytest.mark.parametrize("allocs,want", [([0, 0, 0], 100.0), ([2, 2], 50.0),
                                         ([4], 0.0), ([4, 0], 50.0)])
def test_reads_the_window(allocs, want):
    assert run.read_layer_metric(METRIC, _window(allocs)) == pytest.approx(want)


def test_nothing_to_read_without_wave_buffers(monkeypatch):
    # the program before wave buffers: saves with no codec.wave_buffer span
    assert run.read_layer_metric(METRIC, _window([0, 0], wave_spans=False)) is None
    obs = _window([0, 0])
    assert run.read_layer_metric(METRIC, {"counters": {"bytes_saved": 0}}) is None
    monkeypatch.delattr(telemetry, "span_stats")
    assert run.read_layer_metric(METRIC, obs) is None


def test_traced_toy_run_reuses_every_buffer(monkeypatch):
    # The cell's own two warm saves (the toy loader keeps one): at the toy
    # size the placement moves the groups' (K, P) after the first save, so
    # the second one sizes the free list, and every save of the window,
    # however many the window holds, takes its buffers from it.
    real = run.load_json

    def load(*parts):
        data = real(*parts)
        warm = data.get("warm_saves")
        data = yardstick_tiny.shrink(data)
        if warm is not None:
            data["warm_saves"] = warm
        return data

    monkeypatch.setattr(run, "load_json", load)
    out = run.run_cell(yardstick_tiny.args("ckpt_save.chameleon", trace=1),
                       require_tpu=False, control=False)
    assert out["correct"], out["checks"]
    assert out["metrics"][METRIC] == {"value": 100.0, "unit": "%"}
