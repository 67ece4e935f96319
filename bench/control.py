"""Run a cell with its control in the program's place, on several seeds.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

Each seed runs the cell as ``bench/run.py`` does (set-up, window at the
cell's own load), then compares the control's answers instead of the
program's: the reference computed in float32 for placement decisions,
and the parity of the previous checkpoint for saved bytes.  Prints one JSON line per seed with
the compared numbers; the control has to come out not correct on every
seed.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    run.use_checkout_cache()
    all_failed = True
    for seed in a.seeds:
        args = argparse.Namespace(workload=a.workload, seed=seed, seconds=a.seconds, trace=0)
        out = run.run_cell(args, control=True)
        if out is None:
            return 2
        all_failed &= not out["correct"]
        print(json.dumps({"workload": a.workload, "seed": seed, "control_correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
