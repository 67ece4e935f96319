"""The measurement entry refuses anything but a TPU."""

import os
import shutil
import subprocess
import sys

from yardstick_tiny import BENCH, ROOT, args, run


def test_cpu_device_exits_nonzero_without_result(capsys, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert run.main(["--workload", "ckpt_save.chameleon", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""
    # The compile cache is the checkout's own, whatever the environment said.
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == os.path.join(ROOT, "results", ".jax_cache")


def test_unknown_cell_is_refused():
    try:
        run.run_cell(args("no_such.cell"))
    except SystemExit as e:
        assert "no workload" in str(e)
    else:
        raise AssertionError("an unknown cell ran")


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ckpt_save.chameleon",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
