"""Helpers for the benchmark's CPU tests: the real cells at toy sizes.

The widths of the saved state are shrunk so that a whole run (set-up,
window, comparison) takes seconds on the CPU.  Everything else is the
cell as committed.
"""

from __future__ import annotations

import argparse
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (os.path.join(ROOT, "src"), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

import run  # noqa: E402

TINY_STATE = dict(hidden_size=256, intermediate_size=512, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=64, num_hidden_layers=1)


def shrink(data: dict) -> dict:
    """A loaded configuration or mix, cut to a toy size."""
    if "num_hidden_layers" in data:
        data.update(TINY_STATE)
    if "warm_saves" in data:
        data["warm_saves"] = min(data["warm_saves"], 1)
    return data


def tiny_loader(monkeypatch) -> None:
    real = run.load_json
    monkeypatch.setattr(run, "load_json", lambda *parts: shrink(real(*parts)))


def args(workload: str, seed: int = 3, seconds: float = 0.3, trace: int = 0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds, trace=trace)


def run_tiny(monkeypatch, workload: str, *, control: bool = False, **kw) -> dict:
    tiny_loader(monkeypatch)
    return run.run_cell(args(workload, **kw), require_tpu=False, control=control)
