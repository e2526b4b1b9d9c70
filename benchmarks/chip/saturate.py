#!/usr/bin/env python3
"""The highest request rate a served cell's program sustains, by a sweep
of offered rates, from which the served mixes' rates are set.

    python3 benchmarks/chip/saturate.py --workload synth50k.serve \\
        --rates 9,9.5,10,60 --seconds 30 --seeds 1,2

For each rate and seed, in one process, it runs the cell as ``run.py``
does with the mix's ``rate_req_per_s`` replaced by the rate, and prints
one JSON line: the requests answered per second of the window, how many
of the requests due in the window were answered by its close and how
long the rest took to drain (a backlog that grows through the window
shows in both), the latency percentiles, the scheduler's window
counters, the check and ``correct``.  The benchmark's runs do not run
this; it needs the chip at the cell's size.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench_run  # first: puts the program's src on the path
import layout


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = layout.benchmark()
    w = layout.cell(bench, args.workload)
    for rate in (float(r) for r in args.rates.split(",") if r):
        traffic = dict(layout.traffic(w["traffic"]), rate_req_per_s=rate)
        for seed in (int(s) for s in args.seeds.split(",") if s):
            res = bench_run.run_cell(bench, args.workload, seed, args.seconds, False,
                                     traffic=traffic, t_start=time.perf_counter())
            info = res["info"]
            print(json.dumps({
                "workload": args.workload, "seed": seed, "rate_offered": rate,
                **{key: info[key] for key in (
                    "requests_per_s", "served_rows_per_s", "requests_answered_by_close",
                    "drain_s", "p50_ms", "p95_ms", "window_s", "counters")},
                "attempted": res["attempted"], "failed": res["failed"],
                "correct": res["correct"], "check": res["check"],
                "device": res["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
