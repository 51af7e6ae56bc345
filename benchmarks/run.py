#!/usr/bin/env python3
"""The benchmark's command: one cell, one new process, one result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, sets the system up from the
seed, warms the cell's shapes, measures for ``--seconds``, compares what
the timed path produced with the configuration's plain reference, and
prints the contract's JSON object as the last line of standard output.
Exits non-zero, printing no result, without the chips the cell asks for.
"""

import time

_T0 = time.time()          # set-up is counted from here: before any import

import os      # noqa: E402
import sys     # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks.harness import main
    sys.exit(main(sys.argv[1:], t_start=_T0))
