#!/usr/bin/env python3
"""Chip smoke test: the MoSKA serving path at TinyLlama-1.1B's published
width (22 layers, d_model 2048, 32 heads, 4 KV heads, vocab 32000, bf16
weights from a seed) on one TPU, through the normal entry point
``repro.launch.serve.main``.

    python chip_smoke.py             # phases a-d on one chip
    python chip_smoke.py --chips 4   # the chunk-sharded disaggregated
                                     # path on four chips, and nothing else

One chip:
  a. ``shared_chunk_attention`` compiled for the chip (``tpu_custom_call``
     in its HLO) against the float32 jnp reference, at TinyLlama width
     with 8 chunks of 2048 tokens.
  b. ``serve.main --full``: slotted cache, Pallas shared attention, a
     16,384-token corpus (8 chunks), 8 slots, 8 requests of 128 tokens,
     16 new tokens each.
  c. The same requests with the default jnp shared attention; the share of
     greedy tokens that agree with b is reported (random weights give
     near-flat logits, so it is not a gate).
  d. The same requests on the paged cache with the host tier
     (``--host-pool-blocks auto``) and the Pallas kernel. Four slots over
     a fixed pool of four requests' pages make the later admissions evict
     earlier prompts' pages to the host tier, so the device-to-host copy
     through the CPU backend runs.
Four chips:
  ``core.disagg`` over a 4-device mesh, the store sharded by chunk, each
  shard routing its own chunks, compared with the single-device batched
  path under the same per-shard routing.

It exits 2 when JAX finds no TPU, and 1 when a phase fails. Only when every
phase passed is the last line of stdout the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``. Wall times are
set-up and smoke times, not speed results. The compilation cache is kept
where ``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
# phase a: TinyLlama width, 8 chunks of 2048 tokens, 16 dispatch slots per
# chunk (8 requests x top-8 routing x capacity factor 2 / 8 chunks)
KERNEL_SHAPE = dict(E=8, cap=16, H=32, KH=4, D=64, C=2048)
# bf16 inputs and output vs a float32 reference: the output is rounded to
# bf16 (relative 2^-9) and p is rounded to bf16 before the PV product
KERNEL_TOL = {"out": 1e-2, "lse": 1e-2}
SERVE_ARGS = ["--full", "--arch", "tinyllama-1.1b", "--corpus-tokens",
              "16384", "--requests", "8", "--prompt-len", "128",
              "--new-tokens", "16", "--max-seq", "256", "--seed", str(SEED)]
# phase d: 4 slots, pool = null page + 4 requests x 9 pages (144 tokens)
PAGED_ARGS = ["--slots", "4", "--kv-layout", "paged", "--num-blocks", "37",
              "--host-pool-blocks", "auto", "--kernel", "pallas"]
DISAGG_TOL = {"out": 1e-2, "lse": 1e-2}


def log(msg: str) -> None:
    print(msg, flush=True)


def _masked_max_err(a, b, valid) -> float:
    d = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
    return float(d[valid].max()) if valid.any() else 0.0


def kernel_check() -> dict:
    """Phase a: the Pallas kernel, compiled, against the float32 jnp
    reference."""
    from repro.core.shared_attention import NEG_INF, _chunk_batched_attention
    from repro.kernels.shared_chunk_attn import shared_chunk_attention
    s = KERNEL_SHAPE
    E, cap, H, KH, D, C = (s[n] for n in ("E", "cap", "H", "KH", "D", "C"))
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    qd = jax.random.normal(keys[0], (E, cap, H, D), jnp.bfloat16)
    k = jax.random.normal(keys[1], (E, KH, C, D), jnp.bfloat16)
    v = jax.random.normal(keys[2], (E, KH, C, D), jnp.bfloat16)
    qmask = jax.random.bernoulli(keys[3], 0.75, (E, cap))
    fn = jax.jit(functools.partial(shared_chunk_attention, interpret=False))
    compiled = fn.lower(qd, k, v, qmask).compile()
    custom_call = "tpu_custom_call" in compiled.as_text()
    out, lse = compiled(qd, k, v, qmask)
    with jax.default_matmul_precision("highest"):
        ref_out, ref_lse = jax.jit(_chunk_batched_attention)(
            qd[:, :, None].astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), qmask)
    valid = np.asarray(qmask)
    res = {
        "tpu_custom_call": custom_call,
        "max_abs_err_out": _masked_max_err(out, ref_out[:, :, 0], valid),
        "max_abs_err_lse": _masked_max_err(lse, ref_lse[:, :, 0], valid),
        "tol": KERNEL_TOL,
        "masked_slots_clean": bool(
            np.all(np.asarray(out, np.float32)[~valid] == 0.0) and
            np.all(np.asarray(lse)[~valid] <= NEG_INF / 2)),
    }
    res["ok"] = (res["max_abs_err_out"] <= KERNEL_TOL["out"] and
                 res["max_abs_err_lse"] <= KERNEL_TOL["lse"] and
                 res["masked_slots_clean"] and custom_call)
    return res


def serve(extra) -> dict:
    """One ``serve.main`` run on a fresh metrics registry."""
    from repro import obs
    from repro.launch import serve as serve_mod
    obs.reset_registry()
    summary = serve_mod.main(SERVE_ARGS + list(extra))
    gens = summary["generations"]
    summary["ok"] = (summary["finished"] == 8 and
                     all(len(g) == 16 for g in gens))
    return summary


def agreement(a, b) -> float:
    pairs = [(x, y) for ga, gb in zip(a, b) for x, y in zip(ga, gb)]
    return sum(x == y for x, y in pairs) / max(len(pairs), 1)


def disagg_check() -> dict:
    """Four chips: chunk-sharded disaggregated attention vs the batched
    path on one device, both with per-shard top-k routing."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import MoSKAConfig
    from repro.core import build_store, route, shared_attention_batched
    from repro.core.disagg import disaggregated_shared_attention
    from repro.core.router import Routing
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    n = mesh.shape["data"]
    E, C, KH, D, H, B, top_k = 16, 2048, 4, 64, 32, 8, 2
    keys = jax.random.split(jax.random.PRNGKey(SEED), 3)
    kv = jax.random.normal(keys[0], (2, 1, E * C, KH, D), jnp.bfloat16)
    store = build_store(kv[0], kv[1], C)
    q = jax.random.normal(keys[1], (B, H, D), jnp.bfloat16)
    cfg = MoSKAConfig(top_k_chunks=top_k)
    chunks = NamedSharding(mesh, P("data"))
    sk, sv, semb = (jax.device_put(x[0], chunks)
                    for x in (store.k, store.v, store.emb))
    owners = {sh.device for sh in sk.addressable_shards}
    rows = sorted(sh.index[0].start for sh in sk.addressable_shards)
    spread = (len(owners) == n == len(jax.devices()) and
              rows == [i * E // n for i in range(n)])
    with jax.set_mesh(mesh):
        out, lse = jax.jit(functools.partial(
            disaggregated_shared_attention, cfg=cfg, mesh=mesh,
            kernel="pallas"))(q, sk, sv, semb)
    # reference: each shard's top-k over its own chunks, on one device
    el = E // n
    parts = [route(q, store.emb[0, i * el:(i + 1) * el], top_k)
             for i in range(n)]
    ids = jnp.concatenate([r.chunk_ids + i * el
                           for i, r in enumerate(parts)], axis=1)
    routing = Routing(ids, jnp.concatenate([r.scores for r in parts], 1),
                      jnp.concatenate([r.full_scores for r in parts], 1))
    ref = jax.jit(functools.partial(
        shared_attention_batched, capacity=B * ids.shape[1],
        kernel="pallas"))(q[:, None], store.k[0], store.v[0], routing)
    every = np.ones((B, H), bool)
    res = {
        "devices": n,
        "store_shards_on_devices": sorted(str(d) for d in owners),
        "store_spread": spread,
        "max_abs_err_out": _masked_max_err(out, ref.out[:, 0], every),
        "max_abs_err_lse": _masked_max_err(lse, ref.lse[:, 0], every),
        "tol": DISAGG_TOL,
    }
    res["ok"] = (spread and res["max_abs_err_out"] <= DISAGG_TOL["out"] and
                 res["max_abs_err_lse"] <= DISAGG_TOL["lse"])
    return res


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_phase(name: str, fn, results: dict):
    log(f"== phase {name}: start")
    t0 = time.perf_counter()
    try:
        res = fn()
    except Exception:  # report the phase and go on to the next one
        traceback.print_exc()
        res = {"ok": False, "error": traceback.format_exc(limit=3)}
    res["wall_s_setup_and_smoke"] = time.perf_counter() - t0
    results[name] = res
    shown = {k: v for k, v in res.items() if k != "generations"}
    log(f"== phase {name}: {'PASS' if res['ok'] else 'FAIL'} "
        f"{json.dumps(shown, default=str)}")
    log(f"   peak_bytes_in_use so far: {peak_bytes()}")
    gc.collect()
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: phases a-d on one chip; 4: the disaggregated "
                         "chunk-sharded phase on four chips only")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import init_compile_cache
    log(f"device kind: {dev.device_kind}; devices: {len(devices)}")
    log(f"compilation cache: {init_compile_cache()}")

    results: dict = {}
    if args.chips == 4:
        run_phase("disagg_4chip", disagg_check, results)
    else:
        run_phase("a_kernel", kernel_check, results)
        b = run_phase("b_serve_pallas",
                      lambda: serve(["--slots", "8", "--kernel", "pallas"]),
                      results)
        c = run_phase("c_serve_jnp", lambda: serve(["--slots", "8"]),
                      results)
        if b.get("generations") and c.get("generations"):
            log(f"   greedy-token agreement b vs c (report, not a gate): "
                f"{agreement(b['generations'], c['generations'])}")
        d = run_phase("d_serve_paged_host_tier", lambda: serve(PAGED_ARGS),
                      results)
        if d.get("ok"):
            d["ok"] = d.get("offload_bytes", 0) > 0
            log(f"   host-tier offload bytes: {d.get('offload_bytes')} "
                f"({'PASS' if d['ok'] else 'FAIL: no page left the chip'})")
        if b.get("generations") and d.get("generations"):
            log(f"   greedy-token agreement b vs d (report, not a gate): "
                f"{agreement(b['generations'], d['generations'])}")
    log(f"peak_bytes_in_use: {peak_bytes()}")
    failed = [n for n, r in results.items() if not r["ok"]]
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
