"""Kernel microbenchmarks (Fig. 2a in numbers): the GEMV->GEMM
transformation measured as arithmetic intensity + wall time of the jnp
reference paths on CPU, plus interpret-mode kernel parity timings.

The paper's claim in roofline terms: per-request GEMV over a shared chunk
has intensity ~O(G); batching N concurrent requests into one GEMM raises it
~O(N*G) — past the v5e ridge point (~240 flops/byte) at modest N.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import build_store, route, shared_attention_batched, \
    shared_attention_gather_ref
from repro.launch.roofline import V5E, peaks


def _time(f, *args, n=5):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else None
    outs = f(*args)
    jax.tree.leaves(outs)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        outs = f(*args)
    jax.tree.leaves(outs)[0].block_until_ready()
    return (time.perf_counter() - t0) / n * 1e6


def run(emit):
    key = jax.random.PRNGKey(0)
    E, C, KH, D, H = 8, 2048, 8, 128, 32
    G = H // KH
    kv = jax.random.normal(key, (1, E * C, KH, D), jnp.float32)
    store = build_store(kv, kv, C)
    kvb_per_chunk = 2 * C * KH * D * 4  # fp32 here

    for N in (1, 8, 64, 256):
        q = jax.random.normal(jax.random.fold_in(key, N), (N, 1, H, D),
                              jnp.float32)
        routing = route(q[:, 0], store.emb[0], 2)
        f_b = jax.jit(lambda q, r: shared_attention_batched(
            q, store.k[0], store.v[0], r))
        f_g = jax.jit(lambda q, r: shared_attention_gather_ref(
            q, store.k[0], store.v[0], r))
        t_b = _time(f_b, q, routing)
        t_g = _time(f_g, q, routing)
        # intensity: flops per byte of shared KV actually read
        flops = 4 * N * 2 * C * H * D       # 2 chunks/request
        bytes_gemv = N * 2 * kvb_per_chunk  # per-request re-read
        active = min(E, N * 2)
        bytes_gemm = active * kvb_per_chunk # read once per active chunk
        emit(f"kernels/shared_attn/N{N}/batched_us", t_b,
             f"intensity={flops/bytes_gemm:.1f}flops_per_byte")
        emit(f"kernels/shared_attn/N{N}/gather_gemv_us", t_g,
             f"intensity={flops/bytes_gemv:.1f}flops_per_byte")
    v5e = peaks(V5E)
    ridge = v5e["peak_flops_bf16"] / v5e["hbm_bw"]
    emit("kernels/v5e_ridge_point_flops_per_byte", 0.0, f"{ridge:.0f}")
