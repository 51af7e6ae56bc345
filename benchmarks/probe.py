#!/usr/bin/env python3
"""Readings for setting a cell's limits (steps 3 to 5 of "How ``correct``
is decided"): in ONE process on the chip, for each of several seeds, the
numbers the program's timed path gives against the plain reference, and
for the first ``--controls`` seeds what the control gives (the reference
at the nearest lower precision, put in the program's place).

    python3 benchmarks/probe.py --workload <cell> --seeds 1,2,3 --controls 3 [--seconds 20]

Training reads its first steps and needs no window; a served cell runs
a window of ``--seconds`` at the cell's own load.  One JSON line per
seed, on standard output and in ``chiprun_out/probe_<cell>.jsonl``.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    import argparse

    from benchmarks import harness

    ap = argparse.ArgumentParser(prog="benchmarks/probe.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control-precision", default=None,
                    help="comma-separated precisions to read as controls "
                         "in place of the one the reference names")
    args = ap.parse_args(argv)
    out_dir = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"probe_{args.workload}.jsonl")
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        try:
            _, cell, _ = harness.open_cell(args.workload, seed, args.seconds,
                                           False)
        except harness.Refused as e:
            print(f"bench: {e}", file=sys.stderr)
            return e.code
        runner = harness.load_runner(cell.config["runner"])
        state = runner.setup(cell, {})
        summary = {}
        if cell.mix["kind"] != "train":
            summary = runner.window(cell, state, harness.Tracer(False, ""))
            summary = summary["summary"]
        served = runner.release(cell, state)
        precisions = (args.control_precision.split(",")
                      if args.control_precision
                      else [cell.reference.CONTROL_PRECISION])
        res, controls = runner.compare(cell, served), {}
        for low in precisions if i < args.controls else ():
            with_low = runner.compare(cell, served, with_control=True,
                                      control_precision=low)
            controls[low] = {**with_low.get("control", {}), "detail": {
                k: v for k, v in with_low.get("detail", {}).items()
                if k.startswith("control_")}}
        line = {"probe": cell.name, "seed": seed,
                "program": res.get("numbers"), "control": controls,
                "detail": res.get("detail"), "error": res.get("error"),
                "window": {k: summary[k] for k in
                           ("requests_completed", "failed") if k in summary}}
        print(json.dumps(line), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
