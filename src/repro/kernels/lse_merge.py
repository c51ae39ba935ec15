"""Pallas TPU kernel: LSE merge of partial attentions.

The combine step of the disaggregated dataflow (Fig. 3): partials from the
Unique-KV path, the routed shared chunks, and remote shards are merged
exactly — softmax over the union of key sets — via exp-weighted averaging
in fp32. Elementwise + row reductions only (VPU work); it exists as a
kernel so the merge can fuse into the collective schedule rather than
bouncing through HBM between partials.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _kernel(o_ref, l_ref, out_ref, lse_ref):
    o = o_ref[...].astype(jnp.float32)           # (P, blk, H, D)
    lse = l_ref[...].astype(jnp.float32)         # (P, blk, H)
    # clamp genuine -inf sentinels to the finite NEG_INF: keeps the
    # all-partials-empty row NaN-free (exp(-inf - -inf) is NaN)
    lse = jnp.maximum(lse, NEG_INF)
    m = jnp.max(lse, axis=0)                     # (blk, H)
    w = jnp.exp(lse - m[None])                   # (P, blk, H)
    denom = jnp.sum(w, axis=0)
    out = jnp.sum(o * w[..., None], axis=0)
    out = out / jnp.maximum(denom, 1e-37)[..., None]
    out_ref[...] = out.astype(out_ref.dtype)
    lse_ref[...] = jnp.where(denom > 0,
                             m + jnp.log(jnp.maximum(denom, 1e-37)), NEG_INF)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def lse_merge(outs: jax.Array, lses: jax.Array, *, block_n: int = 256,
              interpret: bool | None = None):
    """outs: (P, N, H, D); lses: (P, N, H) -> (out (N,H,D), lse (N,H))."""
    P, N, H, D = outs.shape
    block_n = min(block_n, N)
    nb = pl.cdiv(N, block_n)

    out, lse = pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((P, block_n, H, D), lambda i: (0, i, 0, 0)),
            pl.BlockSpec((P, block_n, H), lambda i: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, H, D), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_n, H), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, H, D), outs.dtype),
            jax.ShapeDtypeStruct((N, H), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=resolve_interpret(interpret),
        name="moska_lse_merge",
    )(outs, lses)
    return out, lse
