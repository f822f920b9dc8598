"""Find a cell's knee: run the cell's traffic at several fixed
rates, one window each, in one process, and print per rate the program
latency of the first and second half of the window's programs and how
many were left unfinished at the close.  The knee is the highest rate
whose second half is no slower than its first (no growing backlog);
the cell's traffic file takes 4/5 of it.

    python3 bench/sweep.py --workload <cell> --rates 0.2,0.4,0.6 \
        --seconds 30
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys

import run as harness

from bench import spec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=5_000_000_000)
    args = ap.parse_args()
    bm = spec.load_benchmark()
    w = spec.cell(bm, args.workload)
    harness.check_devices(w["chips"])
    harness.use_cache()
    for rate in map(float, args.rates.split(",")):
        res = harness.run_cell(bm, w, args.seed, args.seconds, False,
                               mix_override={"rate_per_s": rate},
                               log=lambda *a: None)
        lat = res["programs"]["latency_s"]
        h = len(lat) // 2
        print(json.dumps({
            "rate": rate, "programs": len(lat),
            "first_half_p50_s": statistics.median(lat[:h]) if h else None,
            "second_half_p50_s": statistics.median(lat[h:]) if lat else None,
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "failed": res["failed"], "correct": res["correct"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
