"""Pallas TPU kernel: MoSKA router chunk scoring.

Relevance of every query group against every shared-chunk embedding —
(G, KH·D) x (E, KH·D)^T as MXU tiles. At corpus scale (16M tokens / 2K
chunk = 8192 chunks) this scoring GEMM is the router's hot loop; top-k
selection stays in XLA (lax.top_k) where it is already optimal.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(q_ref, e_ref, s_ref, *, scale: float):
    q = q_ref[...].astype(jnp.float32)           # (blk_g, F)
    e = e_ref[...].astype(jnp.float32)           # (blk_e, F)
    s_ref[...] = jax.lax.dot_general(
        q, e, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("block_g", "block_e",
                                             "interpret"))
def router_scores(q: jax.Array, emb: jax.Array, *, block_g: int = 128,
                  block_e: int = 512,
                  interpret: bool | None = None) -> jax.Array:
    """q: (G, H, D); emb: (E, KH, D) -> scores (G, E) fp32.

    Each query head scores its kv head's embedding (GQA-aligned); summing
    over heads is folded into the contraction by tiling q to (G, KH*g*D)
    and emb to (E, KH*g*D) with the embedding repeated per group head.
    """
    G, H, D = q.shape
    E, KH, _ = emb.shape
    g = H // KH
    scale = 1.0 / math.sqrt(D)
    qf = q.reshape(G, H * D)
    # repeat each kv-head embedding for its g query heads -> (E, H, D)
    ef = jnp.repeat(emb, g, axis=1).reshape(E, H * D)

    block_g = min(block_g, G)
    block_e = min(block_e, E)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=(pl.cdiv(G, block_g), pl.cdiv(E, block_e)),
        in_specs=[
            pl.BlockSpec((block_g, H * D), lambda i, j: (i, 0)),
            pl.BlockSpec((block_e, H * D), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_g, block_e), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((G, E), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
        name="moska_router_scores",
    )(qf, ef)
