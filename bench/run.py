"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``); the mix's ``kind`` names the
runner, ``bench/kinds/<kind>.py``.  The run builds the system from the
seed, warms up every shape the window uses (that is ``setup_s``),
measures back to back for ``--seconds`` and then compares what the
window produced with the plain references.  ``--trace 1`` profiles the
window and reports the cell's per-layer metrics, each computed by its
own reader ``bench/layer_metrics/<metric>.py``; ``--trace 0`` reports
the end-to-end metrics.  The last line of standard output is one JSON
object.  Without a TPU, or with fewer chips than the cell asks for, the
run prints no result and exits with 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for path in (os.path.join(ROOT, "src"), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
#: jax's persistent compilation cache: a fixed directory inside the
#: checkout (listed in .gitignore), whatever the environment names, so
#: that only a cell's first run in a checkout compiles and two checkouts
#: never share compiled programs.
CACHE_DIR = os.path.join(ROOT, "results", ".jax_cache")


def use_checkout_cache() -> None:
    """Point jax at :data:`CACHE_DIR`; call before jax is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_of(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"bench: no workload named {name!r} in BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def end_to_end_of(spec: dict, cell: str) -> list[dict]:
    return [m for m in spec["end_to_end"] if _applies(m, cell)]


def per_layer_of(spec: dict, cell: str) -> list[dict]:
    e2e = {m["name"] for m in end_to_end_of(spec, cell)}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in e2e)]


def load_by_name(folder: str, name: str):
    """The module in ``bench/<folder>/<name>.py`` (names may hold dots)."""
    path = os.path.join(BENCH, folder, f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(f"bench_{folder}_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_kind(kind: str):
    """The runner of a traffic kind: ``bench/kinds/<kind>.py``."""
    return load_by_name("kinds", kind)


def read_layer_metric(name: str, obs: dict):
    """Call ``read(obs)`` of ``bench/layer_metrics/<name>.py``."""
    return load_by_name("layer_metrics", name).read(obs)


class CompileLog:
    """Backend compiles and persistent-cache hits, from jax's own events,
    counted apart for the measured window."""

    def __init__(self):
        self.in_window = False
        self.counts = {"setup": {"compiles": 0, "cache_hits": 0},
                       "window": {"compiles": 0, "cache_hits": 0}}

    def _slot(self):
        return self.counts["window" if self.in_window else "setup"]

    def on_duration(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self._slot()["compiles"] += 1

    def on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self._slot()["cache_hits"] += 1


def trace_options(jax):
    """Device events only: at any host level the host CPU device's own
    XLA operations (the decision kernels) swamp the trace; the host side
    comes from the benchmark's spans instead."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    return opts


def device_info(jax, devices, n: int) -> dict:
    used = devices[:n]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak}


def run_cell(args, *, require_tpu: bool = True, control: bool = False) -> dict | None:
    """Everything but the printing; returns the result object (``None``
    when the machine has not the chips the cell asks for)."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cell = cell_of(spec, args.workload)
    config = load_json(BENCH, "configs", f"{cell['config']}.json")
    mix = load_json(BENCH, "traffic", f"{cell['traffic']}.json")

    import jax
    import jax.monitoring

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print(f"bench: the cell needs {cell['chips']} TPU chip(s); jax finds "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return None
    peaks = load_json(BENCH, "peaks.json")["devices"]
    kind = devices[0].device_kind
    if require_tpu and kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in bench/peaks.json")

    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    jax.monitoring.register_event_listener(log.on_event)
    from repro.core import shapes  # noqa: F401  (points jax at the compile cache)

    import trace_reduce

    runner = load_kind(mix["kind"]).Runner(config, mix, args.seed)
    runner.setup()
    setup_s = time.perf_counter() - T_START

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    if trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=trace_options(jax))
    runner.spans.start()
    log.in_window = True
    res = runner.measure(float(args.seconds))
    log.in_window = False
    summary = None
    if trace_dir:
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        summary = trace_reduce.reduce_file(files[0], runner.spans.spans)
        shutil.rmtree(trace_dir, ignore_errors=True)

    device = device_info(jax, devices, cell["chips"])
    checks = runner.verify(control=control)

    if args.trace:
        obs = {"elapsed_s": res["elapsed_s"], "counters": res["counters"],
               "work": res["work"], "trace": summary, "peaks": peaks.get(kind, {})}
        metrics = {}
        for m in per_layer_of(spec, cell["name"]):
            value = read_layer_metric(m["name"], obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = res["elapsed_s"]
    else:
        values = dict(res["end_to_end"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in end_to_end_of(spec, cell["name"])}
    out = {
        "correct": all(v <= 0 for v in checks.values()),
        "attempted": res["attempted"],
        "failed": res["failed"],
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["info"] = {"compiles": log.counts, "counters": res["counters"], "work": res["work"]}
    out["checks"] = {name: {"value": v, "limit": 0} for name, v in checks.items()}
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def emit(out: dict) -> None:
    """Compiles first, the compared numbers last on standard error, then
    the result line last on standard output."""
    print(f"bench: compiles {json.dumps(out['info']['compiles'])}", flush=True)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(f"correct {str(out['correct']).lower()}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    use_checkout_cache()
    out = run_cell(parse_args(argv))
    if out is None:
        return 2
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
