"""Read the numbers that decide ``correct`` over many seeds, in one
process, under the committed limits: the program's widest logit gap per
seed, and on the first few seeds the float8 control in the program's
place, which must come out not correct.  Their readings set each cell's
limit in ``bench/limits/<cell>.json`` (steps 4 and 5 of how ``correct``
is decided; the readings and the limit are in ``PERF.md``).

    python3 bench/calibrate.py --workload <cell> --seeds 12 --controls 3 \
        --seconds 15 [--first-seed N]

Each seed runs the cell's whole timed path (set-up, a short window at
the cell's own load, drain) and prints one JSON line.  Not part of the
benchmark's own runs.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as harness   # bench/run.py, beside this file

from bench import spec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=15)
    ap.add_argument("--first-seed", type=int, default=4_000_000_000)
    args = ap.parse_args()
    bm = spec.load_benchmark()
    w = spec.cell(bm, args.workload)
    harness.check_devices(w["chips"])
    harness.use_cache()
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        control = "fp8" if i < args.controls else None
        seen = {}
        res = harness.run_cell(
            bm, w, seed, args.seconds, False, control=control,
            log=lambda name, rec: seen.setdefault(name, rec))
        print(json.dumps({
            "seed": seed, "control": control, "correct": res["correct"],
            "program_logit_gap": seen["reference"]["logit_gap"],
            "checks": res["checks"],
            "peak": res["device"]["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
