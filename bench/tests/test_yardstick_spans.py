"""The readers of the program's save spans: each on a synthetic window,
nothing to read where nothing was saved or the program keeps no spans,
and all of them in a traced run of the save cell at a toy size."""

import pytest

import yardstick_tiny  # noqa: F401  (puts bench/ and src/ on the path)
from yardstick_tiny import run, run_tiny

import save_spans  # noqa: E402
from repro import telemetry  # noqa: E402

READERS = {
    "ckpt_d2h_s_per_GB.save": ("ckpt.d2h",),
    "ckpt_split_s_per_GB.save": ("ckpt.split",),
    "ckpt_put_wait_s_per_GB.save": ("ckpt.put_wait",),
    "codec_copy_s_per_GB.save": ("codec.stage", "codec.concat", "codec.assemble"),
    "codec_transfer_s_per_GB.save": ("codec.h2d", "codec.d2h"),
}
COPY_METRIC = "host_copy_bytes_pct.save"
SAVED = 1000  # user bytes of one synthetic save


def _save(step: int, scale: int) -> None:
    """One synthetic save: every span of the save path, with ``scale``
    times the bytes of the step's own spans."""
    with telemetry.span("ckpt.save", SAVED, request=step):
        for name in ("ckpt.d2h", "ckpt.split", "ckpt.place", "ckpt.put_wait"):
            with telemetry.span(name, scale):
                pass
        with telemetry.span("ckpt.encode"):
            for name in ("codec.stage", "codec.concat", "codec.h2d", "codec.wait",
                         "codec.d2h", "codec.assemble"):
                with telemetry.span(name, scale):
                    pass
    with telemetry.span("ckpt.put", scale, request=step):
        pass


@pytest.fixture
def window():
    """Two warm saves, then a window of three; the obs of that window."""
    telemetry.reset(prefilter_counters=False, matrix_caches=False, compile_census=False)
    for step in range(5):
        _save(step, 100 if step < 2 else 1)
    yield {"counters": {"bytes_saved": 3 * SAVED}}
    telemetry.reset(prefilter_counters=False, matrix_caches=False, compile_census=False)


def _window_seconds(names):
    per = telemetry.span_stats()["requests"][2:]
    return sum(r["spans"][n]["seconds"] for r in per for n in names)


@pytest.mark.parametrize("name", sorted(READERS))
def test_seconds_readers_read_the_window(window, name):
    got = run.read_layer_metric(name, window)
    assert got == pytest.approx(_window_seconds(READERS[name]) / (3 * SAVED / 1e9), rel=1e-9)


def test_copy_bytes_read_the_window(window):
    # seven host copies of 1 byte in each of three saves, over 3000 bytes
    assert run.read_layer_metric(COPY_METRIC, window) == pytest.approx(100.0 * 21 / 3000)
    assert save_spans.window(window)["ckpt.save"]["count"] == 3


@pytest.mark.parametrize("name", sorted(READERS) + [COPY_METRIC])
def test_nothing_to_read(window, monkeypatch, name):
    assert run.read_layer_metric(name, {"counters": {"bytes_saved": 0}}) is None
    # the newest saves do not add up to the window
    assert run.read_layer_metric(name, {"counters": {"bytes_saved": 2500}}) is None
    # a program without spans (the tree before them)
    monkeypatch.delattr(telemetry, "span_stats")
    assert run.read_layer_metric(name, window) is None


def test_traced_toy_run_reports_every_span_metric(monkeypatch):
    out = run_tiny(monkeypatch, "ckpt_save.chameleon", trace=1)
    assert out["correct"], out["checks"]
    for name in list(READERS) + [COPY_METRIC, "encode_host_s_per_GB.save",
                                 "place_s_per_GB.save"]:
        assert out["metrics"][name]["value"] > 0, name
    # a save copies every user byte at least four times on the host:
    # device to host, tobytes, staging, concatenation
    assert out["metrics"][COPY_METRIC]["value"] > 400
