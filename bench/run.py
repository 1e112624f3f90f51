"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell table is ``BENCHMARK.json`` at the checkout's root.  Set-up
(weights from ``--seed`` on the device, the engine, the compile of both
step profiles, the traffic before the window) is timed from process
start; then the window runs for ``--seconds``.  After it, a sample of the
requests it served is compared with the plain reference.  The last line
of stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, and with ``--trace 1`` ``breakdown``; last of
all ``check``, each number compared beside its limit (also the last lines
of stderr).  Without a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_PROCESS))
