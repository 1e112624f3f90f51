"""The control of the comparison that decides ``correct``, and the lower
reading, at a cell's own size, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed: one run of the cell (a short window at the cell's own
load), then the plain reference over the sampled requests twice: once at
float32 (the program's widest gap, the lower reading's sample) and once
with every weight matrix rounded through fp8 e4m3 (the gap of the fp8
model's first choice, the control).  The control has to come out above
the limit in ``configs/<config>.json``, and every program reading below
it.  One JSON line per seed; the benchmark's own runs never run this.
"""

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def main(argv) -> int:
    import argparse
    from bench.harness import ROOT, run_cell
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = run_cell(ROOT, args.workload, seed, args.seconds, False,
                       time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program_gap": out["check"]["max_logit_gap"],
                          "control_gap": out["control_gap"],
                          "tokens": out["check"]["served_tokens_compared"]
                          ["value"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
