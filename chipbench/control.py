#!/usr/bin/env python3
"""Readings behind a cell's check limit, on the chip, in one process.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3,... \\
        --control-seeds 1,2,3 --seconds <s>

For each seed it serves the cell's mix through a short window at the
cell's own load, exactly as ``run.py`` does, and compares the same sample
of finished requests with the plain reference: each of the program's
numbers (``run.gap_stats``) is a lower reading of
its limit. For each control seed it also runs the
control -- the reference in the precision one step below the
configuration's -- over the same prompts and served tokens, and reads the
gap of the token the control puts first at each position: the same
numbers, read from those gaps, are upper readings, and the control's gaps
go through the cell's own check (``run.compare``), whose verdict
(``control_correct``) has to come out false.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]

import numpy as np  # noqa: E402

from chipbench import run  # noqa: E402


def readings(cell, system, seed: int, seconds: float, control: bool,
             fault=None) -> dict:
    import jax
    t = time.perf_counter()
    sv = run.serve_cell(cell, system, seed, seconds, fault=fault,
                        t_start=t)
    seqs, corpus = run.take_sample(cell, sv, seed)
    counters = sv.server.counters()
    dropped = counters.get("moska/dropped_queries", {}).get("value", 0.0)
    sv.server.close()
    del sv
    gc.collect()
    jax.clear_caches()
    out = {"seed": seed, "requests": len(seqs), "dropped_routes": dropped}
    if not seqs:
        out["error"] = "no request finished"
        return out
    targets = np.concatenate([s for _, s in seqs])
    ref = run.reference_logits(cell, seed, corpus, seqs)
    gaps = run.logit_gaps(ref, targets)
    out.update(tokens=int(gaps.size), program=run.gap_stats(gaps),
               program_correct=run.compare(cell.limits, gaps)[1])
    if control:
        ctl = run.reference_logits(cell, seed, corpus, seqs, lowp=True)
        top = np.asarray(ctl.argmax(axis=1))
        cg = run.logit_gaps(ref, top)
        out.update(control=run.gap_stats(cg),
                   control_correct=run.compare(cell.limits, cg)[1])
        del ctl
    del ref
    out["seconds"] = time.perf_counter() - t
    return out


def main(argv=None, bench_path=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload, bench_path)
    dev = run.device_info()
    if dev is None or dev["platform"] != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return run.NO_CHIP
    system = run.prepare(cell)
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for s in (int(x) for x in args.seeds.split(",")):
        r = readings(cell, system, s, args.seconds, s in ctl)
        rows.append(r)
        print(json.dumps(r), flush=True)
    summary = {"workload": args.workload, "device": dev,
               "seeds": sum("program" in r for r in rows),
               "control_seeds": sum("control" in r for r in rows),
               "control_correct": [r["control_correct"] for r in rows
                                   if "control" in r]}
    for k in run.gap_stats(np.zeros(1)):
        prog = [r["program"][k] for r in rows if "program" in r]
        ctrl = [r["control"][k] for r in rows if "control" in r]
        summary[k] = {"lower_reading": max(prog) if prog else None,
                      "upper_reading": min(ctrl) if ctrl else None}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
