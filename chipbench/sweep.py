#!/usr/bin/env python3
"""Find the knee of an open-loop cell: offer its mix at a rising ladder of
rates, in one process with one set-up, and report for each step of the
ladder the requests admitted and completed, the tokens per second, the TTFT
tail and the requests still queued (due, not yet given a slot) at the
step's end. The steps follow one another without draining, so each starts
from the load the last one left; once a rate is past what the system
sustains, its queue grows and stays. The knee is the highest rate whose
step ends with no more than ``max(2, 5%)`` of its requests queued. Used
once, when a cell is defined; the cell's mix then carries 0.8 of the knee
as a fixed rate.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 4,8,12,16

The last line of standard output is ``{"knee_per_s": ..., "rate_per_s":
...}``, the rate being 0.8 of the knee.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]

from chipbench import latency, run  # noqa: E402


def sustained(row: dict) -> bool:
    return row["queued_at_end"] <= max(2, 0.05 * row["due"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    dev = run.device_info()
    if dev is None or dev["platform"] != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return run.NO_CHIP
    system = run.prepare(cell)
    server = system.Server(cell.conf, cell.mix, args.seed)
    drv = run.Feeder(server, cell.mix, args.seed, server.vocab_size)
    run.warm_up(drv, server, cell.mix, args.seed, server.vocab_size)
    drv.recs.clear()
    drv.waves.clear()
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell.mix)
        mix["arrival"]["rate_per_s"] = rate
        drv.mix = mix
        t0 = time.perf_counter() + 0.01
        t1 = t0 + args.seconds
        drv.schedule_open(t0, args.seconds, stream=10 + i)
        drv.drive(t1)
        data = run.run_data(cell, drv, t0, t1, {}, {}, 0.0, {})
        queued = sum(1 for r in drv.recs if r.due <= t1 and
                     (r.admitted is None or r.admitted > t1))
        queued += sum(1 for r in drv.pending if r.due <= t1)
        done = [r for r in drv.recs if r.stamps and r.req.done
                and t0 <= r.stamps[-1] <= t1]
        tt = latency.ttft_s(data)
        itl = latency.itl_s(data)
        row = {
            "rate_per_s": rate, "due": len(data.recs),
            "admitted_per_s": sum(1 for r in drv.recs if r.admitted is not None
                                  and t0 <= r.admitted <= t1) / args.seconds,
            "completed_per_s": len(done) / args.seconds,
            "tokens_per_s": sum(1 for r in drv.recs for s in r.stamps
                                if t0 <= s <= t1) / args.seconds,
            "ttft_p50_ms": 1e3 * latency.percentile(tt, 50) if tt else None,
            "ttft_p95_ms": 1e3 * latency.percentile(tt, 95) if tt else None,
            "itl_p50_ms": 1e3 * latency.percentile(itl, 50) if itl else None,
            "itl_p95_ms": 1e3 * latency.percentile(itl, 95) if itl else None,
            "live_at_end": sum(1 for r in drv.inflight if r.admitted is not None
                               and not r.req.done),
            "queued_at_end": queued}
        row["sustained"] = sustained(row)
        print(json.dumps(row), flush=True)
        if not row["sustained"]:
            break
        knee = rate
    print(json.dumps({"knee_per_s": knee,
                      "rate_per_s": None if knee is None
                      else round(0.8 * knee, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
