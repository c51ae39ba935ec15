#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its
configuration (``chipbench/configs/<config>.json``), its traffic mix
(``chipbench/traffic/<traffic>.json``), its check limits
(``chipbench/cells/<cell>.json``) and each metric's reader
(``chipbench/metrics/<metric>.py``) are found by name, so a new cell, mix,
configuration or metric is a new file and a new entry.

Set-up builds the weights on the device from the seed, registers the
corpus, compiles every prefill program the mix's prompt lengths can reach
and the decode step, and runs the mix for its ``warmup_s``. The measured
window follows at once, for ``--seconds``. With ``--trace 0`` the last line
of standard output carries the cell's end-to-end metrics; with
``--trace 1`` the profiler records the window's first ``TRACE_S`` seconds
and the line carries the per-layer metrics instead.

After the window, the process reads its peak device memory, frees the
system under test and compares a seeded sample of the finished requests
with the plain reference (``chipbench/configs/<reference>.py``): the gaps
by which each served token's reference logit lies below the reference's
best, reduced to the numbers the cell's file names (the widest gap, a
percentile, the mean), must stay within the cell's limits.

Exit codes: 0 with a result line; 3 when JAX finds no TPU or fewer chips
than the cell asks for (nothing is printed on standard output); 1 on any
other failure.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import latency, loadgen  # noqa: E402

TRACE_S = 8.0          # traced stretch at the start of the window
NO_CHIP = 3


class WindowClosed(Exception):
    """Raised from the wave hook to leave the engine's loop at the end of
    the window."""


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    entry: dict
    conf: dict
    mix: dict
    limits: dict
    bench: dict
    root: str

    def metrics(self, kind: str) -> List[dict]:
        """The cell's end-to-end (``kind='end_to_end'``) or per-layer
        metrics, as entries of BENCHMARK.json."""
        name = self.entry["name"]
        return [m for m in self.bench[kind]
                if "workloads" not in m or name in m["workloads"]]


def load_cell(workload: str, bench_path: Optional[str] = None) -> Cell:
    bench_path = bench_path or os.path.join(ROOT, "BENCHMARK.json")
    root = os.path.dirname(os.path.abspath(bench_path))
    with open(bench_path) as f:
        bench = json.load(f)
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(entries)}")
    entry = entries[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, confs[entry["config"]]["file"])) as f:
        conf = json.load(f)
    mix = loadgen.load(entry["traffic"], os.path.join(root, "chipbench"))
    with open(os.path.join(root, "chipbench", "cells",
                           f"{workload}.json")) as f:
        limits = json.load(f)
    return Cell(entry, conf, mix, limits, bench, root)


def _module(root: str, sub: str, name: str):
    path = os.path.join(root, "chipbench", sub, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{sub}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# driving the system
# ---------------------------------------------------------------------------

@dataclass
class Rec:
    """One request as the harness sees it."""
    spec: loadgen.Spec
    due: float
    req: object = None
    submitted: float = 0.0
    admitted: Optional[float] = None   # start of the wave that prefilled it
    stamps: List[float] = field(default_factory=list)
    client: int = -1

    @property
    def served(self) -> np.ndarray:
        return np.asarray(self.req.generated, np.int32)


@dataclass
class Wave:
    start: float
    end: float
    prefill_lens: List[int]
    decode_ctx: List[int]


def jax_annotation(name: str):
    import jax.profiler
    return jax.profiler.TraceAnnotation(name)


class Feeder:
    """Feeds a mix to the server and stamps every token with the host time
    at which the engine's loop handed it back (the end of its wave)."""

    def __init__(self, server, mix: dict, seed: int, vocab: int):
        self.server, self.mix, self.seed, self.vocab = server, mix, seed, vocab
        self.recs: List[Rec] = []
        self.inflight: List[Rec] = []
        self.pending: List[Rec] = []          # open loop, sorted by due
        self.waves: List[Wave] = []
        self.stop_at = float("inf")
        self.wave_start = 0.0
        self.closed_pool: List[loadgen.Spec] = []
        self.closed_next = 0
        self.on_time: List = []                # [(t, fn)] fired once

    # -- arrivals ------------------------------------------------------
    def schedule_open(self, t0: float, seconds: float, stream: int) -> None:
        n = loadgen.open_count(self.mix, seconds)
        specs = loadgen.requests(self.mix, n, self.seed, self.vocab, stream)
        offs = loadgen.arrival_offsets(self.mix, n, stream)
        self.pending.extend(Rec(s, t0 + o)
                            for s, o in zip(specs, offs) if o < seconds)
        self.pending.sort(key=lambda r: r.due)

    def start_closed(self, t0: float) -> None:
        self.closed_pool = loadgen.requests(self.mix, 2048, self.seed,
                                            self.vocab, stream=3)
        for c in range(self.mix["arrival"]["clients"]):
            self._client_send(c, t0)

    def _client_send(self, client: int, due: float) -> None:
        spec = self.closed_pool[self.closed_next % len(self.closed_pool)]
        self.closed_next += 1
        rec = Rec(spec, due, client=client)
        self._submit(rec, due)

    def _submit(self, rec: Rec, now: float) -> None:
        rec.req = self.server.submit(rec.spec.prompt, rec.spec.max_new_tokens)
        rec.submitted = now
        self.recs.append(rec)
        self.inflight.append(rec)

    def submit_due(self, now: float) -> None:
        i = 0
        while i < len(self.pending) and self.pending[i].due <= now:
            self._submit(self.pending[i], now)
            i += 1
        del self.pending[:i]

    # -- the wave hook -------------------------------------------------
    def on_wave(self) -> None:
        with jax_annotation("bench.wave_hook"):
            self._on_wave()

    def _on_wave(self) -> None:
        now = time.perf_counter()
        cur, self.inflight = self.inflight, []
        pre, dec, kept = [], [], []
        for rec in cur:
            n_old, n_new = len(rec.stamps), len(rec.req.generated)
            if n_new > n_old:
                rec.stamps.extend([now] * (n_new - n_old))
                plen = len(rec.spec.prompt)
                if n_old == 0:
                    rec.admitted = self.wave_start
                    pre.append(plen)
                if n_new > max(n_old, 1):
                    dec.append(plen + n_new - 1)
            if not rec.req.done:
                kept.append(rec)
            elif rec.client >= 0 and now < self.stop_at:
                self._client_send(rec.client, now)
        self.inflight = kept + self.inflight
        self.waves.append(Wave(self.wave_start, now, pre, dec))
        while self.on_time and self.on_time[0][0] <= now:
            self.on_time.pop(0)[1]()
        self.submit_due(now)
        if now >= self.stop_at:
            raise WindowClosed
        self.wave_start = time.perf_counter()

    def drive(self, until: float) -> None:
        """Serve until ``until`` (host time), or, with ``until`` infinite,
        until nothing is left to do."""
        self.stop_at = until
        self.server.set_wave_hook(self.on_wave)
        try:
            while True:
                now = time.perf_counter()
                if now >= until:
                    return
                while self.on_time and self.on_time[0][0] <= now:
                    self.on_time.pop(0)[1]()
                self.submit_due(now)
                if self.server.busy:
                    self.wave_start = time.perf_counter()
                    with jax_annotation("bench.engine_run"):
                        self.server.serve()
                elif until == float("inf") and not self.pending:
                    return
                else:
                    nxt = until
                    if self.pending:
                        nxt = min(nxt, self.pending[0].due)
                    if self.on_time:
                        nxt = min(nxt, self.on_time[0][0])
                    with jax_annotation("bench.idle_wait"):
                        time.sleep(max(0.0, nxt - now))
        except WindowClosed:
            return


# ---------------------------------------------------------------------------
# what a run hands the metric readers
# ---------------------------------------------------------------------------

@dataclass
class RunData:
    conf: dict
    mix: dict
    peaks: dict
    seconds: float                 # length of the window read
    t0: float
    t1: float
    recs: List[Rec]                # requests due in [t0, t1)
    all_recs: List[Rec]
    waves: List[Wave]              # waves that ended in [t0, t1]
    counters: Dict[str, dict]      # registry change over [t0, t1]
    setup_s: float
    trace: Optional[dict] = None


def counter_delta(a: Dict[str, dict], b: Dict[str, dict]) -> Dict[str, dict]:
    """What each counter and histogram of ``b`` added since ``a``."""
    out = {}
    for name, snap in b.items():
        old = a.get(name, {})
        if snap["kind"] == "counter":
            out[name] = {"kind": "counter",
                         "value": snap["value"] - old.get("value", 0.0)}
        elif snap["kind"] == "histogram":
            out[name] = {"kind": "histogram",
                         "count": snap["count"] - old.get("count", 0),
                         "sum": snap["sum"] - old.get("sum", 0.0)}
        else:
            out[name] = snap
    return out


def run_data(cell: Cell, drv: Feeder, t0: float, t1: float, c0, c1,
             setup_s: float, peaks: dict, trace=None) -> RunData:
    recs = [r for r in drv.recs if t0 <= r.due < t1]
    waves = [w for w in drv.waves if t0 <= w.end <= t1]
    return RunData(cell.conf, cell.mix, peaks, t1 - t0, t0, t1, recs,
                   drv.recs, waves, counter_delta(c0, c1), setup_s, trace)


def read_metrics(cell: Cell, kind: str, data: RunData) -> Dict[str, dict]:
    out = {}
    for m in cell.metrics(kind):
        mod = _module(cell.root, "metrics", m["name"])
        v = mod.read(data)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the output check
# ---------------------------------------------------------------------------

def check_sample(recs: List[Rec], seed: int, tokens: int,
                 max_requests: int) -> List[Rec]:
    """A seeded sample of finished requests, the longest among them, of
    about ``tokens`` served tokens."""
    done = [r for r in recs if r.req.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.generated),
                                       len(r.spec.prompt)))
    rng = np.random.default_rng([seed, 0xC4])
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    out, n = [longest], len(longest.req.generated)
    for r in rest:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(r)
        n += len(r.req.generated)
    return out


def gap_stats(gaps: np.ndarray) -> Dict[str, float]:
    """The numbers a cell's check may compare, from the per-token gaps: the
    widest, the 99th percentile, the mean, and the share of served tokens
    that are not the reference's first choice."""
    return {"max_logit_gap": float(gaps.max()),
            "p99_logit_gap": float(np.percentile(gaps, 99)),
            "mean_logit_gap": float(gaps.mean()),
            "off_argmax_share": float(np.mean(gaps > 0))}


def compare(limits: dict, gaps: np.ndarray):
    """The check: each number the cell's file names under ``compare``
    against its limit, and the count of tokens compared against
    ``min_tokens_compared``. Returns (numbers with limits, correct)."""
    stats = gap_stats(gaps)
    check = {k: {"value": stats[k], "limit": lim}
             for k, lim in limits["compare"].items()}
    check["tokens_compared"] = {"value": int(gaps.size),
                                "limit": limits["min_tokens_compared"]}
    ok = (all(stats[k] <= lim for k, lim in limits["compare"].items())
          and gaps.size >= limits["min_tokens_compared"])
    return check, bool(ok)


def logit_gaps(ref_logits, targets: np.ndarray) -> np.ndarray:
    """Per row: the reference's best logit minus its logit of the token
    in ``targets`` (0 where they agree)."""
    import jax.numpy as jnp
    t = jnp.asarray(targets, jnp.int32)
    best = ref_logits.max(axis=1)
    got = jnp.take_along_axis(ref_logits, t[:, None], axis=1)[:, 0]
    return np.asarray(best - got)


# ---------------------------------------------------------------------------

def device_info() -> Optional[dict]:
    import jax
    devs = jax.devices()
    if not devs:
        return None
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def peak_bytes() -> Optional[int]:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def init_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache`` (the path the program's own
    ``launch/compile_cache.py`` uses)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def warm_up(drv: Feeder, server, mix: dict, seed: int, vocab: int) -> int:
    """Compile every prefill program the mix's prompt lengths can reach,
    and the decode step, by serving one short request per program."""
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    lens = server.prefill_lengths(lo, hi)
    rng = np.random.default_rng([seed, 0x3A])
    from chipbench import corpus as corpus_lib
    for n in lens:
        spec = loadgen.Spec(corpus_lib.zipf_segments(n, vocab, rng), 2)
        drv._submit(Rec(spec, time.perf_counter()), time.perf_counter())
    drv.drive(float("inf"))
    return len(lens)


@dataclass
class Served:
    """What one served window leaves behind."""
    drv: Feeder
    server: object
    t0: float
    t1: float
    snaps: dict
    setup_s: float
    n_prog: int
    t_compiled: float
    trace_dir: Optional[str] = None

    @property
    def window(self) -> List[Rec]:
        return [r for r in self.drv.recs if self.t0 <= r.due < self.t1]


def prepare(cell: Cell, use_cache: bool = True):
    """The system's module, with the program on the path and the compile
    cache set."""
    if use_cache:
        print(f"compilation cache: {init_cache(cell.root)}", file=sys.stderr)
    src = os.path.join(cell.root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    return importlib.import_module(f"chipbench.systems.{cell.conf['system']}")


def serve_cell(cell: Cell, system, seed: int, seconds: float,
               trace: bool = False, fault=None, t_start: float = T_PROCESS
               ) -> Served:
    """Set up the system from ``seed``, warm it up and serve the cell's mix
    through a window of ``seconds``."""
    import jax
    server = system.Server(cell.conf, cell.mix, seed)
    if fault is not None:
        fault(server)
    vocab = server.vocab_size
    drv = Feeder(server, cell.mix, seed, vocab)
    n_prog = warm_up(drv, server, cell.mix, seed, vocab)
    t_compiled = time.perf_counter()
    drv.recs.clear()
    drv.waves.clear()

    warm = float(cell.mix.get("warmup_s", 0.0))
    t_w = time.perf_counter()
    t0 = t_w + warm
    t1 = t0 + seconds
    if cell.mix["arrival"]["kind"] == "closed":
        drv.start_closed(t_w)
    else:
        if warm > 0:
            drv.schedule_open(t_w, warm, stream=1)
        drv.schedule_open(t0, seconds, stream=2)

    out = Served(drv, server, t0, t1, {}, t0 - t_start, n_prog, t_compiled)

    def mark(name):
        def f():
            if name in out.snaps:
                return
            out.snaps[name] = (time.perf_counter(), server.counters())
            if name == "t0" and trace:
                out.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0    # no per-call Python events
                jax.profiler.start_trace(out.trace_dir,
                                         profiler_options=opts)
                out.snaps["trace_start"] = (time.perf_counter(), None)
            if name == "trace_end" and trace:
                jax.profiler.stop_trace()
        return f

    drv.on_time = [(t0, mark("t0"))]
    if trace:
        drv.on_time.append((min(t1, t0 + TRACE_S), mark("trace_end")))
    drv.on_time.append((t1, mark("t1")))
    drv.drive(t1)
    for name, _ in [("t0", 0)] + [("trace_end", 0)] * trace + [("t1", 0)]:
        mark(name)()
    return out


def reference_logits(cell: Cell, seed: int, corpus: np.ndarray, seqs,
                     lowp: bool = False):
    """The plain reference's logits for ``seqs``, padded to the sample's
    most requests and the mix's ``max_seq`` so that every run's reference
    programs have one shape."""
    ref = _module(cell.root, "configs", cell.conf["reference"])
    return ref.logits(cell.conf, seed, corpus, seqs, lowp=lowp,
                      rows=cell.limits["check_requests"],
                      seq_len=cell.mix["max_seq"])


def take_sample(cell: Cell, served: Served, seed: int):
    """(prompt, served tokens) of the check's sample, and the corpus."""
    lim = cell.limits
    sample = check_sample(served.window, seed, lim["check_tokens"],
                          lim["check_requests"])
    seqs = [(np.asarray(r.spec.prompt, np.int32), r.served) for r in sample]
    return seqs, np.asarray(served.server.corpus)


def main(argv=None, require_tpu: bool = True, use_cache: bool = True,
         bench_path: Optional[str] = None, fault=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, bench_path)

    dev = device_info()
    if require_tpu and (dev is None or dev["platform"] != "tpu"):
        print(f"run: needs a TPU; JAX found "
              f"{dev['platform'] if dev else 'no device'}", file=sys.stderr)
        return NO_CHIP
    if dev["count"] < cell.entry["chips"]:
        print(f"run: cell {args.workload} needs {cell.entry['chips']} "
              f"chips; JAX found {dev['count']}", file=sys.stderr)
        return NO_CHIP
    system = prepare(cell, use_cache)
    from chipbench.peaks import peaks as peaks_of
    # off the chip (the harness's own CPU tests) the v5e's peaks stand in
    peaks = peaks_of(dev["kind"] if require_tpu else "TPU v5 lite")

    seed = args.seed
    sv = serve_cell(cell, system, seed, args.seconds, bool(args.trace),
                    fault)
    mem_peak = peak_bytes()
    window = sv.window
    lag = [r.submitted - r.due for r in window]
    lag = lag or [0.0]
    print(f"generator lag (s) over {len(window)} requests: p50 "
          f"{latency.percentile(lag, 50)} p99 {latency.percentile(lag, 99)} "
          f"max {max(lag)}; set-up {sv.setup_s} s "
          f"({sv.n_prog} prefill programs compiled by "
          f"{sv.t_compiled - T_PROCESS} s)", file=sys.stderr)

    out = {"correct": False, "attempted": len(window), "failed": 0}
    breakdown = None
    if args.trace:
        from chipbench import trace_reduce
        ts, te = sv.snaps["trace_start"][0], sv.snaps["trace_end"][0]
        red = trace_reduce.reduce(trace_reduce.find_xplane(sv.trace_dir))
        shutil.rmtree(sv.trace_dir, ignore_errors=True)
        red["window_s"] = te - ts
        data = run_data(cell, sv.drv, ts, te, sv.snaps["t0"][1],
                        sv.snaps["trace_end"][1], sv.setup_s, peaks, red)
        metrics = read_metrics(cell, "per_layer", data)
        breakdown = {"device_ops": trace_reduce.top_items(red["ops"]),
                     "idle_gaps": red["gaps"]}
        print("device programs (count, s): " + json.dumps(red["modules"]),
              file=sys.stderr)
        print("idle by host span (s): " + json.dumps(red["gap_total"]),
              file=sys.stderr)
        print(f"device time (s): leaf ops {red['busy_s']}, host waits "
              f"{red['host_wait_s']}, every op with containers "
              f"{red['all_ops_s']}, window {red['window_s']}",
              file=sys.stderr)
        dev_out = dict(dev, busy_s=red["busy_s"], window_s=red["window_s"])
    else:
        data = run_data(cell, sv.drv, sv.t0, sv.t1, sv.snaps["t0"][1],
                        sv.snaps["t1"][1], sv.setup_s, peaks)
        metrics = read_metrics(cell, "end_to_end", data)
        dev_out = dict(dev)
    dev_out["memory_peak_bytes"] = mem_peak
    print("program counters over the window: " + json.dumps(
        {k: v for k, v in data.counters.items()
         if k.startswith(("moska/dropped", "moska/dispatched",
                          "engine/decode_steps", "engine/prefills"))}),
        file=sys.stderr)

    # -- the check, once the system is freed --------------------------
    lim = cell.limits
    seqs, corpus = take_sample(cell, sv, seed)
    drained = sum(r.req.done for r in window)
    sv.server.close()
    del sv, data
    gc.collect()
    import jax
    jax.clear_caches()      # compiled programs keep the engine alive
    check = {}
    t_ref = time.perf_counter()
    try:
        if not seqs:
            raise RuntimeError("no request finished in the window")
        lg = reference_logits(cell, seed, corpus, seqs)
        gaps = logit_gaps(lg, np.concatenate([s for _, s in seqs]))
        print("logit gaps: " + json.dumps(gap_stats(gaps)), file=sys.stderr)
        check, out["correct"] = compare(lim, gaps)
    except Exception as e:  # the check failed to run: not correct
        import traceback
        traceback.print_exc()
        check["error"] = {"value": repr(e)[:300], "limit": "none"}
    print(f"reference check: {time.perf_counter() - t_ref} s over "
          f"{len(seqs)} requests ({drained} of {len(window)} window "
          f"requests finished)", file=sys.stderr)

    out["metrics"] = metrics
    out["device"] = dev_out
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    for k, v in check.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
