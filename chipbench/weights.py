"""Seeded random weights of a dense decoder, made by the benchmark.

Every leaf is drawn from its own key, ``fold_in(fold_in(run key, leaf
index), layer)``, as a uniform variate scaled to the leaf's standard
deviation and rounded to the served dtype. The program gets all layers at
once from one jitted call (``stacked``); the plain reference draws the same
leaves one layer at a time (``layer``), so it never needs the whole model
in float32 and takes nothing that the program made.

Names follow the published checkpoints' roles, with matrices stored as
(in, out) so that ``x @ w`` applies them. A norm leaf ``s`` stands for the
published RMSNorm weight ``1 + s``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1
BIAS_STD = 0.02

LAYER_LEAVES = ("input_norm", "post_norm", "wq", "wk", "wv", "wo", "bq", "bk",
                "bv", "w_gate", "w_up", "w_down")
GLOBAL_LEAVES = ("embed", "final_norm", "unembed")


def dims(conf: dict) -> Dict[str, int]:
    """The sizes a dense decoder needs, from a configuration file."""
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {
        "L": conf["num_hidden_layers"], "d": d, "H": h,
        "KH": conf["num_key_value_heads"],
        "D": conf.get("head_dim") or d // h,
        "F": conf["intermediate_size"], "V": conf["vocab_size"],
        "bias": bool(conf.get("qkv_bias", False)),
        "tied": bool(conf["tie_word_embeddings"]),
    }


def run_key(seed: int) -> jax.Array:
    """A key from any whole-number seed (64 bits and more are fine)."""
    a, b = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) & 0x7FFFFFFF),
                              int(b) & 0x7FFFFFFF)


def _spec(m: Dict[str, int], name: str):
    d, H, KH, D, F, V = (m[k] for k in ("d", "H", "KH", "D", "F", "V"))
    return {
        "input_norm": ((d,), NORM_STD), "post_norm": ((d,), NORM_STD),
        "wq": ((d, H * D), d ** -0.5), "wk": ((d, KH * D), d ** -0.5),
        "wv": ((d, KH * D), d ** -0.5), "wo": ((H * D, d), (H * D) ** -0.5),
        "bq": ((H * D,), BIAS_STD), "bk": ((KH * D,), BIAS_STD),
        "bv": ((KH * D,), BIAS_STD),
        "w_gate": ((d, F), d ** -0.5), "w_up": ((d, F), d ** -0.5),
        "w_down": ((F, d), F ** -0.5),
        "embed": ((V, d), d ** -0.5), "final_norm": ((d,), NORM_STD),
        "unembed": ((V, d), d ** -0.5),
    }[name]


BLOCK_ELEMS = 1 << 24


def _draw(key, leaf_id: int, layer, shape, std, dtype):
    """One leaf, drawn in blocks of rows (each from its own key) so that
    the float32 draws in flight stay small."""
    k = jax.random.fold_in(jax.random.fold_in(key, leaf_id), layer)
    rows, per_row = shape[0], int(np.prod(shape[1:], dtype=np.int64))
    blk = max(1, min(rows, BLOCK_ELEMS // max(per_row, 1)))
    nblk = -(-rows // blk)

    def one(b):
        u = jax.random.uniform(jax.random.fold_in(k, b),
                               (blk,) + tuple(shape[1:]), jnp.float32,
                               -1.0, 1.0)
        return (u * jnp.float32(std * math.sqrt(3.0))).astype(dtype)

    out = jax.lax.map(one, jnp.arange(nblk))
    return out.reshape((nblk * blk,) + tuple(shape[1:]))[:rows]


def layer_names(m: Dict[str, int]):
    return [n for n in LAYER_LEAVES if m["bias"] or n not in ("bq", "bk", "bv")]


def global_names(m: Dict[str, int]):
    return [n for n in GLOBAL_LEAVES if not (m["tied"] and n == "unembed")]


def _layer(key, m, layer, dtype):
    return {n: _draw(key, LAYER_LEAVES.index(n), layer, *_spec(m, n), dtype)
            for n in layer_names(m)}


def _globals(key, m, dtype):
    return {n: _draw(key, 100 + GLOBAL_LEAVES.index(n), 0, *_spec(m, n),
                     dtype)
            for n in global_names(m)}


@partial(jax.jit, static_argnums=(1, 2))
def _stacked(key, mk, dtype):
    m = dict(mk)
    # one layer at a time, so that only one layer's draws are in flight
    layers = jax.lax.map(lambda l: _layer(key, m, l, dtype),
                         jnp.arange(m["L"]))
    return layers, _globals(key, m, dtype)


def stacked(seed: int, m: Dict[str, int], dtype=jnp.bfloat16):
    """All weights in one jitted call: (layers stacked on axis 0, globals)."""
    return _stacked(run_key(seed), tuple(sorted(m.items())), jnp.dtype(dtype))


@partial(jax.jit, static_argnums=(1, 3))
def _one_layer(key, mk, layer, dtype):
    return _layer(key, dict(mk), layer, dtype)


@partial(jax.jit, static_argnums=(1, 2))
def _global(key, mk, dtype):
    return _globals(key, dict(mk), dtype)


def layer(seed: int, m: Dict[str, int], i: int, dtype=jnp.bfloat16):
    """Layer ``i``'s weights alone, bit for bit as ``stacked`` draws them."""
    return _one_layer(run_key(seed), tuple(sorted(m.items())),
                      jnp.int32(i), jnp.dtype(dtype))


def globals_(seed: int, m: Dict[str, int], dtype=jnp.bfloat16):
    return _global(run_key(seed), tuple(sorted(m.items())), jnp.dtype(dtype))
