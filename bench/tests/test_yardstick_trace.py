"""The trace reduction: busy union, idle gaps named by the host spans."""

import os

import pytest

import yardstick_tiny  # noqa: F401  (puts bench/ on the path)

import trace_reduce  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_reduce_synthetic_planes():
    planes = [
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_gf_bitmatmul(3)", 100, 50), ("jit_make(1)", 300, 20)]),
            ("XLA Ops", [("coding", 100, 30), ("coding", 120, 30), ("fill", 300, 20)]),
        ]),
        ("/host:CPU", [("python", [("ignored", 0, 1000)])]),
    ]
    spans = [("bench.save", 0, 400), ("bench.put", 200, 50)]
    out = trace_reduce.reduce_planes(planes, spans)
    # Busy: [100, 150) and [300, 320) -> 70 ns.
    assert out["busy_s"] == pytest.approx(70e-9)
    assert out["modules_s"] == {"jit_gf_bitmatmul": 50e-9, "jit_make": 20e-9}
    assert trace_reduce.kernel_seconds(out, trace_reduce.CODING_KERNEL) == pytest.approx(50e-9)
    assert dict(out["device_ops"]) == {"coding": 60e-9, "fill": 20e-9}
    # Idle inside [0, 400): [0,100) [150,300) [320,400); the middle gap's
    # midpoint (225) lies in bench.put.
    gaps = dict(out["idle_gaps"])
    assert gaps["bench.put"] == pytest.approx(150e-9)
    assert gaps["bench.save"] == pytest.approx(180e-9)


def test_reduce_recorded_chip_trace():
    """A toy save traced on a v5e: the coding kernel is the program
    ``jit_gf_bitmatmul`` on ``/device:TPU:0`` and its Pallas call the
    operation ``%gf_bitmatmul.N`` (a ``tpu_custom_call``)."""
    out = trace_reduce.reduce_file(os.path.join(DATA, "tiny_save.xplane.pb"),
                                   [("bench.save", 0, 2 * 10**9)])
    kernel = trace_reduce.kernel_seconds(out, trace_reduce.CODING_KERNEL)
    assert 0 < kernel <= out["busy_s"]
    assert any(name.startswith("gf_bitmatmul") for name, _ in out["device_ops"])
    assert out["idle_gaps"][0][0] == "bench.save"
