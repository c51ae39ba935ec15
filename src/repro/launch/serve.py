"""Serving launcher: MoSKA engine over a shared corpus.

    PYTHONPATH=src python -m repro.launch.serve --metrics-out metrics.json

Registers a synthetic domain corpus (precomputed shared KV chunks), submits
a stream of requests against it, and reports scheduler/throughput metrics
from the process-global observability registry (``repro.obs``). The default
invocation is the fast dry-run path: a reduced config small enough for CPU
smoke runs; pass ``--full`` for the published architecture. Everything runs
on JAX's default device — one chip, or the CPU — with no mesh and no
sharding rules; ``--kernel pallas`` runs the decode step's shared attention
through the Pallas kernel, compiled for a TPU and interpreted on the CPU.
JAX's persistent compilation cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``.

``--metrics-out PATH`` dumps the full registry at exit — scheduler
occupancy/affinity, dispatch capacity-utilization, decode-latency
histograms, and trace spans — as JSON (or line protocol for ``.lp``/
``.txt`` paths). See README "Metrics & tracing" for the naming and bucket
conventions.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro import obs
from repro.configs import get_config
from repro.core.scheduler import latency_stats, wave_stats
from repro.data.pipeline import CorpusSpec, synthesize_corpus
from repro.launch.compile_cache import init_compile_cache
from repro.models.model import build_model
from repro.serving.engine import EngineConfig, ServingEngine


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--full", action="store_true",
                    help="run the unreduced architecture (default: reduced "
                         "dry-run path)")
    ap.add_argument("--reduced", action="store_true",
                    help="deprecated: reduced is now the default")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--corpus-tokens", type=int, default=512)
    ap.add_argument("--kernel", default="jnp", choices=["jnp", "pallas"],
                    help="decode-step shared attention: the jnp path "
                         "(default) or the Pallas kernel")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-donate", action="store_true",
                    help="disable cache donation (copying decode steps; "
                         "for differential debugging)")
    ap.add_argument("--prefill-buckets", default="auto", metavar="SPEC",
                    help="'auto' (default), 'none' (exact lengths), or a "
                         "comma-separated bucket list, e.g. '16,32,64'")
    ap.add_argument("--kv-layout", default="slotted",
                    choices=["slotted", "paged"],
                    help="unique-KV layout: 'slotted' (per-slot max_seq "
                         "slab) or 'paged' (block pool + block tables; "
                         "bit-identical generations, less HBM)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV page (paged layout; must divide "
                         "max-seq)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="fixed page-pool size (paged layout; default: "
                         "grow on demand)")
    ap.add_argument("--host-pool-blocks", default="0", metavar="N|auto",
                    help="host memory tier capacity in blocks (paged "
                         "layout): LRU-evicted prefix pages are offloaded "
                         "to host RAM and swapped back on a later hit "
                         "instead of being rebuilt; 0 disables the tier; "
                         "'auto' sizes it from the workload's prefix "
                         "working set via core.analytical."
                         "size_host_pool_blocks")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="max in-flight host->device prefetch transfers "
                         "for predicted next-wave admissions (paged layout "
                         "with a host tier); 0 disables prefetching")
    ap.add_argument("--no-spec-append", action="store_true",
                    help="disable speculative decode-boundary page "
                         "allocation (paged layout; for differential "
                         "debugging — generations are identical either "
                         "way)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="run the wave's host-side bookkeeping after the "
                         "device sync instead of inside the dispatch "
                         "window (for differential debugging / stall "
                         "measurement baselines)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the metrics registry (JSON; .lp/.txt for "
                         "line protocol) at exit")
    ap.add_argument("--metrics-flush-every", type=int, default=0,
                    metavar="N",
                    help="also rewrite --metrics-out atomically every N "
                         "decode waves (streaming export for long serves); "
                         "0 disables")
    args = ap.parse_args(argv)
    if args.metrics_flush_every and not args.metrics_out:
        ap.error("--metrics-flush-every requires --metrics-out")

    if args.host_pool_blocks == "auto":
        if args.kv_layout != "paged":
            ap.error("--host-pool-blocks auto requires --kv-layout paged")
        from repro.core.analytical import size_host_pool_blocks
        host_pool_blocks = size_host_pool_blocks(
            workset_tokens=args.requests * args.prompt_len,
            block_size=args.block_size,
            device_pool_blocks=args.num_blocks,
            active_tokens=args.slots * (args.prompt_len + args.new_tokens))
    else:
        host_pool_blocks = int(args.host_pool_blocks)

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    if args.corpus_tokens < cfg.moska.chunk_size:
        ap.error(f"--corpus-tokens {args.corpus_tokens} is shorter than one "
                 f"shared chunk of {cfg.name} ({cfg.moska.chunk_size} "
                 "tokens)")
    init_compile_cache()

    if args.prefill_buckets == "none":
        buckets = None
    elif args.prefill_buckets == "auto":
        buckets = "auto"
    else:
        buckets = [int(b) for b in args.prefill_buckets.split(",")]

    with obs.span("serve.init", arch=args.arch):
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(args.seed))
        eng = ServingEngine(cfg, params, EngineConfig(
            max_slots=args.slots, max_seq=args.max_seq,
            kernel=None if args.kernel == "jnp" else args.kernel,
            donate_cache=not args.no_donate, prefill_buckets=buckets,
            kv_layout=args.kv_layout, block_size=args.block_size,
            num_blocks=args.num_blocks,
            host_pool_blocks=host_pool_blocks,
            prefetch_depth=args.prefetch_depth,
            spec_append=not args.no_spec_append,
            overlap_waves=not args.no_overlap))

    exporter = None
    if args.metrics_flush_every:
        exporter = obs.StreamingExporter(args.metrics_out,
                                         every=args.metrics_flush_every)
        eng.wave_hooks.append(exporter.tick)

    corpus = synthesize_corpus(CorpusSpec(
        "domain-0", args.corpus_tokens, cfg.vocab_size, seed=args.seed))
    nchunks = eng.register_corpus("domain-0", corpus)
    reg_span = eng.registry.spans[-1]
    print(f"registered corpus domain-0: {nchunks} chunks "
          f"({reg_span.duration_s:.1f}s)")

    rng = np.random.default_rng(args.seed)
    for _ in range(args.requests):
        eng.submit(rng.integers(0, cfg.vocab_size,
                                args.prompt_len).tolist(),
                   max_new_tokens=args.new_tokens, corpus_id="domain-0")

    done = eng.run()

    reg = eng.registry
    decode_lat = reg.histogram("engine/decode_step_latency_s",
                               obs.LATENCY_EDGES_S)
    summary = {
        "finished": len(done),
        "tokens": int(reg.counter("engine/tokens_generated").value),
        "decode_steps": int(reg.counter("engine/decode_steps").value),
        "tokens_per_s": reg.gauge("engine/last_run_tokens_per_s").value,
        "decode_step_p50_s": decode_lat.quantile(0.5),
        "slot_occupancy": reg.gauge("scheduler/slot_occupancy").value,
        "affinity_hits": reg.counter("scheduler/affinity_hits").value,
        "prefill_buckets": list(eng.prefill_buckets or ()),
        "prefill_compile_count":
            int(reg.gauge("engine/prefill_compile_count").value),
        "decode_cache_bytes_copied":
            reg.gauge("engine/decode_cache_bytes_copied").value,
        "kv_layout": args.kv_layout,
        "hbm_high_water_bytes":
            reg.gauge("engine/hbm_high_water_bytes").value,
        "wave": wave_stats(done),
        "requests": latency_stats(done),
        "admit_wait_p95_s": reg.histogram(
            "scheduler/admit_wait_s", obs.LATENCY_EDGES_S).quantile(0.95),
        "backend_compiles": int(reg.counter("jax/backend_compiles").value),
    }
    if args.kv_layout == "paged":
        summary["host_pool_blocks"] = host_pool_blocks
        summary["swap_in_hits"] = int(
            reg.counter("kvcache/swap_in_hits").value)
        summary["offload_bytes"] = int(
            reg.counter("kvcache/offload_bytes").value)
        summary["offload_admissions"] = int(
            reg.counter("scheduler/offload_admissions").value)
        summary["prefetch_issued"] = int(
            reg.counter("kvcache/prefetch_issued").value)
        summary["prefetch_hits"] = int(
            reg.counter("kvcache/prefetch_hits").value)
        summary["spec_pages_alloc"] = int(
            reg.counter("kvcache/spec_pages_alloc").value)
        summary["decode_stall_sum_s"] = reg.histogram(
            "engine/decode_stall_s", obs.LATENCY_EDGES_S).sum
    if exporter is not None:
        summary["metrics_flushes"] = exporter.flushes
    print(json.dumps(summary, indent=1))
    if args.metrics_out:
        obs.dump(args.metrics_out, reg)
        print(f"metrics registry -> {args.metrics_out}")
    # greedy tokens per request, in submission order (returned, not printed)
    summary["generations"] = [r.generated
                              for r in sorted(done, key=lambda r: r.uid)]
    return summary


if __name__ == "__main__":
    main()
