"""Dry-run lowering specs: (architecture x input shape) -> jit-able step
function + ShapeDtypeStruct arguments with NamedShardings (no allocation).

Shape semantics (assignment):
  train_4k     train_step  (loss+grad+AdamW) seq 4096, global batch 256
  prefill_32k  prefill     seq 32768, batch 32 (writes the unique cache)
  decode_32k   serve_step  ONE token, unique KV cache of 32768/request,
               batch 128; MoSKA-enabled archs also carry a 2M-token shared
               store (the paper's feature is first-class at decode)
  long_500k    serve_step  ONE token, 524288-token context, batch 1.
               Dense/VLM archs: the context IS the shared chunk store and
               attention is MoSKA-routed (sub-quadratic — the paper's own
               mechanism); SSM/hybrid: native O(1)-state decode;
               whisper-tiny: SKIPPED (enc-dec, no 500K decode analogue).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import (AUDIO, DENSE, HYBRID, MOE, SSM, VLM,
                                InputShape, INPUT_SHAPES, ModelConfig)
from repro.core.shared_kv import abstract_store
from repro.models.model import Model, build_model
from repro.sharding import specs as sp
from repro.training.optimizer import adamw_init
from repro.training.train_loop import TrainLoopConfig, make_train_step

# tokens in the attached shared store per shape (MoSKA-enabled archs)
DECODE32K_SHARED_TOKENS = 2 * 2**20     # 1024 x 2048-token chunks
LONG500K_UNIQUE_BUF = 2048              # generated-token buffer at 500K


@dataclass
class LoweringSpec:
    arch: str
    shape: str
    fn: Callable                     # positional-args step function
    args: Tuple[Any, ...]            # SDS pytrees with shardings
    rules: sp.LogicalRules
    note: str = ""


class Skip(Exception):
    """(arch, shape) combination is intentionally unsupported."""


def _ns(mesh, pspec):
    return NamedSharding(mesh, pspec)


def _sds(shape, dtype, mesh, pspec):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=_ns(mesh, pspec))


def _shard_tree(tree, pspec_tree, mesh):
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                          sharding=_ns(mesh, s)),
        tree, pspec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _batch_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _resolve_guarded(rules, names, mesh, shape):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sp._resolve(rules, names, mesh.axis_names, shape, sizes)


def _abstract_params(model: Model, rules, mesh):
    params = model.abstract_params()
    pspecs = sp.param_pspecs(params, rules, mesh)
    return _shard_tree(params, pspecs, mesh), pspecs


# ---------------------------------------------------------------------------
# cache / store sharding
# ---------------------------------------------------------------------------

_CACHE_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    # dense KVCache fields (L, B, S, KH·D); seq dim over model =
    # flash-decoding KV split
    "k": (None, "batch", "kv_seq", "kv_heads"),
    "v": (None, "batch", "kv_seq", "kv_heads"),
    "length": ("batch",),
    "offset": ("batch",),
    # ssm
    "conv": (None, "batch", None, "state"),
    "state": (None, "batch", None, None, None),
    # hybrid
    "ring_k": (None, "batch", "kv_seq", "kv_heads", None),
    "ring_v": (None, "batch", "kv_seq", "kv_heads", None),
    "ring_pos": (None, "batch", None),
    "lru": (None, "batch", "state"),
    # hybrid conv is (n_rec, B, 3, lw) = same "conv" key
    # whisper
    "self_k": (None, "batch", "kv_seq", "kv_heads", None),
    "self_v": (None, "batch", "kv_seq", "kv_heads", None),
    "cross_k": (None, "batch", "kv_seq", "heads", None),
    "cross_v": (None, "batch", "kv_seq", "heads", None),
}

_STORE_AXES = {
    "k": (None, "chunks", "kv_heads", "chunk_seq", None),
    "v": (None, "chunks", "kv_heads", "chunk_seq", None),
    "emb": (None, "chunks", "kv_heads", None),
    "chunk_positions": (None,),
    "k_scale": (None, "chunks", "kv_heads", "chunk_seq"),
    "v_scale": (None, "chunks", "kv_heads", "chunk_seq"),
}


def _cache_sds(cache, rules, mesh, table=None):
    table = table or _CACHE_AXES

    def one(path, leaf):
        name = None
        for p in reversed(path):
            if hasattr(p, "key") or hasattr(p, "name"):
                name = getattr(p, "key", None) or getattr(p, "name", None)
                break
        names = table.get(name, (None,) * leaf.ndim)
        names = tuple(names[:leaf.ndim]) + (None,) * (leaf.ndim - len(names))
        ps = _resolve_guarded(rules, names, mesh, leaf.shape)
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=_ns(mesh, ps))

    return jax.tree_util.tree_map_with_path(one, cache)


def _store_sds(cfg: ModelConfig, shared_tokens: int, rules, mesh):
    store = abstract_store(cfg, shared_tokens)
    return _cache_sds(store._asdict(), rules, mesh, _STORE_AXES), store


# ---------------------------------------------------------------------------
# per-shape builders
# ---------------------------------------------------------------------------

def _train_batch_sds(cfg: ModelConfig, ishape: InputShape, rules, mesh):
    B, S = ishape.global_batch, ishape.seq_len
    bp = _resolve_guarded(rules, ("batch", None), mesh, (B, S))
    batch = {
        "tokens": _sds((B, S), jnp.int32, mesh, bp),
        "targets": _sds((B, S), jnp.int32, mesh, bp),
        "mask": _sds((B, S), jnp.float32, mesh, bp),
    }
    if cfg.family == VLM:
        Pn = cfg.encoder.frontend_seq
        St = S - Pn
        bp2 = _resolve_guarded(rules, ("batch", None), mesh, (B, St))
        batch["tokens"] = _sds((B, St), jnp.int32, mesh, bp2)
        batch["targets"] = _sds((B, St), jnp.int32, mesh, bp2)
        batch["mask"] = _sds((B, St), jnp.float32, mesh, bp2)
        ep = _resolve_guarded(rules, ("batch", None, None), mesh,
                              (B, Pn, cfg.encoder.frontend_dim))
        batch["frontend_embeds"] = _sds((B, Pn, cfg.encoder.frontend_dim),
                                        jnp.bfloat16, mesh, ep)
    elif cfg.family == AUDIO:
        F = cfg.encoder.frontend_seq
        ep = _resolve_guarded(rules, ("batch", None, None), mesh,
                              (B, F, cfg.encoder.frontend_dim))
        batch["frontend_embeds"] = _sds((B, F, cfg.encoder.frontend_dim),
                                        jnp.bfloat16, mesh, ep)
    return batch


def build_train(arch: str, cfg: ModelConfig, ishape: InputShape,
                mesh: Mesh, variant: Optional[str] = None) -> LoweringSpec:
    zero1 = False
    if variant and "zero1" in variant:
        # ZeRO-1: weights TP-only (replicated over data; grads all-reduce
        # naturally), optimizer moments stay fully sharded over data — the
        # one param all-gather per step replaces the pathological per-layer
        # gradient gathers (§Perf, mistral iteration 3)
        zero1 = True
        variant = ",".join(k for k in variant.split(",") if k != "zero1") \
            or None
    rules = sp.apply_variant(sp.TRAIN_RULES, variant)
    model = build_model(cfg)
    if zero1:
        params_rules = sp.apply_variant(rules, "weights_resident")
        params_sds, _ = _abstract_params(model, params_rules, mesh)
        _, opt_pspecs = _abstract_params(model, rules, mesh)
        pspecs = opt_pspecs
        rules = params_rules
    else:
        params_sds, pspecs = _abstract_params(model, rules, mesh)
    opt = jax.eval_shape(adamw_init, params_sds)
    opt_sds = opt._replace(
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=_ns(mesh, P())),
        mu=_shard_tree(opt.mu, pspecs, mesh),
        nu=_shard_tree(opt.nu, pspecs, mesh))
    batch = _train_batch_sds(cfg, ishape, rules, mesh)
    loop_cfg = TrainLoopConfig(num_steps=1000, remat=True)
    fn = make_train_step(model, loop_cfg)
    return LoweringSpec(arch, ishape.name, fn,
                        (params_sds, opt_sds, batch), rules)


def build_prefill(arch: str, cfg: ModelConfig, ishape: InputShape,
                  mesh: Mesh, variant: Optional[str] = None) -> LoweringSpec:
    rules = sp.apply_variant(sp.SERVE_RULES, variant)
    model = build_model(cfg)
    params_sds, _ = _abstract_params(model, rules, mesh)
    B, S = ishape.global_batch, ishape.seq_len
    if cfg.family == VLM:
        Pn = cfg.encoder.frontend_seq
        toks = _sds((B, S - Pn), jnp.int32, mesh,
                    _resolve_guarded(rules, ("batch", None), mesh,
                                     (B, S - Pn)))
    else:
        toks = _sds((B, S), jnp.int32, mesh,
                    _resolve_guarded(rules, ("batch", None), mesh, (B, S)))
    cache = model.init_cache(B, S, abstract=True)
    cache_sds = _cache_sds(
        cache._asdict() if hasattr(cache, "_asdict") else cache, rules, mesh)
    if hasattr(cache, "_asdict"):
        from repro.kvcache.cache import KVCache
        cache_sds = KVCache(**cache_sds)
    args = [params_sds, toks, cache_sds]
    note = ""
    if cfg.family in (VLM, AUDIO):
        F = cfg.encoder.frontend_seq
        ep = _resolve_guarded(rules, ("batch", None, None), mesh,
                              (B, F, cfg.encoder.frontend_dim))
        fe = _sds((B, F, cfg.encoder.frontend_dim), jnp.bfloat16, mesh, ep)
        fn = lambda p, t, c, f: model.prefill(p, t, c, frontend_embeds=f)
        args.append(fe)
        note = "stub frontend embeddings"
    else:
        fn = lambda p, t, c: model.prefill(p, t, c)
    return LoweringSpec(arch, ishape.name, fn, tuple(args), rules, note)


def build_decode(arch: str, cfg: ModelConfig, ishape: InputShape,
                 mesh: Mesh, variant: Optional[str] = None) -> LoweringSpec:
    long_ctx = ishape.name == "long_500k"
    rules = sp.apply_variant(
        sp.LONGCTX_RULES if long_ctx else sp.SERVE_RULES, variant)
    B = ishape.global_batch
    note = ""

    if long_ctx:
        if cfg.family == AUDIO:
            raise Skip("enc-dec audio has no 500K-token decode analogue "
                       "(DESIGN.md §4)")
        if cfg.family in (DENSE, VLM, MOE):
            if not cfg.moska.enabled:
                raise Skip("full-attention arch without MoSKA routing is "
                           "quadratic at 500K")
            note = ("500K context = MoSKA shared chunk store, routed "
                    "sub-quadratic attention (the paper's mechanism)")

    model = build_model(cfg)
    params_sds, _ = _abstract_params(model, rules, mesh)
    toks = _sds((B,), jnp.int32, mesh,
                _resolve_guarded(rules, ("batch",), mesh, (B,)))

    if long_ctx:
        cache_len = LONG500K_UNIQUE_BUF if cfg.family in (DENSE, VLM, MOE) \
            else ishape.seq_len
        shared_tokens = ishape.seq_len
    else:
        cache_len = ishape.seq_len
        shared_tokens = DECODE32K_SHARED_TOKENS

    cache = model.init_cache(B, cache_len, abstract=True)
    is_nt = hasattr(cache, "_asdict")
    cache_sds = _cache_sds(cache._asdict() if is_nt else cache, rules, mesh)
    if is_nt:
        from repro.kvcache.cache import KVCache
        cache_sds = KVCache(**cache_sds)

    use_store = (cfg.moska.enabled and cfg.family in (DENSE, VLM, MOE)
                 and (long_ctx or True))
    if cfg.family == AUDIO:
        use_store = False   # cross-KV store path exercised in tests/examples
    if cfg.family in (SSM, HYBRID):
        use_store = False

    if use_store:
        store_sds_dict, _ = _store_sds(cfg, shared_tokens, rules, mesh)
        from repro.core.shared_kv import SharedKVStore
        store_sds = SharedKVStore(**store_sds_dict)
        fn = lambda p, t, c, s: model.decode_step(p, t, c, store=s)
        args = (params_sds, toks, cache_sds, store_sds)
        note = note or f"MoSKA store: {shared_tokens} shared tokens"
    else:
        fn = lambda p, t, c: model.decode_step(p, t, c)
        args = (params_sds, toks, cache_sds)
    return LoweringSpec(arch, ishape.name, fn, args, rules, note)


# config-level §Perf variants (vs sharding-rule variants in specs.VARIANTS)
CFG_VARIANTS = {
    "bigblock": dict(attn_block_k=4096),
    "smallblock": dict(attn_block_k=512),
    "remat_dots": dict(remat_policy="dots"),
    "no_remat": dict(remat_policy="none"),
}


def build(arch: str, shape_name: str, mesh: Mesh,
          variant: Optional[str] = None) -> LoweringSpec:
    cfg = get_config(arch)
    rule_keys = []
    if variant:
        for key in variant.split(","):
            if key == "int8store":
                # beyond-paper: int8 shared-KV store (FP8 parity on TPU)
                cfg = dataclasses.replace(cfg, moska=dataclasses.replace(
                    cfg.moska, kv_quant="int8"))
            elif key in CFG_VARIANTS:
                cfg = dataclasses.replace(cfg, **CFG_VARIANTS[key])
            else:
                rule_keys.append(key)
        variant = ",".join(rule_keys) or None
    ishape = INPUT_SHAPES[shape_name]
    if ishape.kind == "train":
        out = build_train(arch, cfg, ishape, mesh, variant=variant)
    elif ishape.kind == "prefill":
        out = build_prefill(arch, cfg, ishape, mesh, variant=variant)
    else:
        out = build_decode(arch, cfg, ishape, mesh, variant=variant)
    return out
