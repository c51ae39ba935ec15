"""Model facade: dispatches the family-specific implementations behind one
API used by training, serving, launch, and tests.

    model = build_model(cfg)
    params = model.init(key)
    loss, metrics = model.train_loss(params, batch)
    cache = model.init_cache(batch_size, max_seq)
    logits, cache = model.prefill(params, tokens, cache, store=...)
    logits, cache = model.decode_step(params, tokens, cache, store=...)

With ``return_stats=True`` the serving programs also return the shared
path's per-layer ``DispatchStats`` (None without a store, and always None
outside the dense family, whose shared path is the only one serving uses).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (AUDIO, DENSE, HYBRID, MOE, SSM, VLM,
                                ModelConfig)
from repro.kvcache.cache import abstract_kv_cache, init_kv_cache
from repro.kvcache.paged import init_paged_kv_cache


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        if cfg.family in (DENSE, VLM, MOE):
            from repro.models import dense as impl
        elif cfg.family == SSM:
            from repro.models import ssm as impl
        elif cfg.family == HYBRID:
            from repro.models import hybrid as impl
        elif cfg.family == AUDIO:
            from repro.models import encdec as impl
        else:
            raise ValueError(cfg.family)
        self._impl = impl

    # ------------------------------------------------------------------
    def init(self, key):
        return self._impl.init_params(self.cfg, key)

    def abstract_params(self, key=None):
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(lambda k: self._impl.init_params(self.cfg, k),
                              key)

    def train_loss(self, params, batch: Dict[str, Any], *, remat: bool = True):
        return self._impl.train_loss(self.cfg, params, batch, remat=remat)

    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16,
                   abstract: bool = False):
        cfg = self.cfg
        if cfg.family in (DENSE, VLM, MOE):
            fn = abstract_kv_cache if abstract else init_kv_cache
            return fn(cfg.num_layers, batch, max_seq, cfg.num_kv_heads,
                      cfg.head_dim, dtype)
        return self._impl.init_cache(cfg, batch, max_seq, dtype,
                                     abstract=abstract)

    def prefill(self, params, tokens, cache, store=None,
                frontend_embeds=None, start_pos: int = 0, true_len=None,
                kernel: Optional[str] = None, return_stats: bool = False):
        # true_len: real prompt length for bucket-padded serving prefill;
        # kernel: shared-attention implementation (dense family only)
        kw = {} if true_len is None else {"true_len": true_len}
        dense = self.cfg.family in (DENSE, VLM, MOE)
        if dense:
            kw["kernel"] = kernel
            kw["return_stats"] = return_stats
        if self.cfg.family in (VLM, AUDIO):
            kw["frontend_embeds"] = frontend_embeds
        out = self._impl.prefill(self.cfg, params, tokens, cache,
                                 store=store, start_pos=start_pos, **kw)
        return (*out, None) if return_stats and not dense else out

    def decode_step(self, params, tokens, cache, store=None, positions=None,
                    kernel: Optional[str] = None, return_stats: bool = False):
        dense = self.cfg.family in (DENSE, VLM, MOE)
        kw = {"return_stats": return_stats} if dense else {}
        out = self._impl.decode_step(self.cfg, params, tokens, cache,
                                     store=store, positions=positions,
                                     kernel=kernel, **kw)
        return (*out, None) if return_stats and not dense else out

    # -- paged KV layout (dense-family only) ---------------------------
    def _require_paged(self, what: str):
        if self.cfg.family not in (DENSE, VLM, MOE):
            raise NotImplementedError(
                f"{what} requires the paged KV layout, which only the "
                f"dense-family caches support (family={self.cfg.family!r}; "
                "use kv_layout='slotted')")

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=jnp.bfloat16):
        self._require_paged("init_paged_cache")
        cfg = self.cfg
        return init_paged_kv_cache(cfg.num_layers, num_blocks, block_size,
                                   cfg.num_kv_heads, cfg.head_dim, dtype)

    def decode_step_paged(self, params, tokens, pool, table, lengths,
                          offsets, store=None,
                          kernel: Optional[str] = None,
                          return_stats: bool = False):
        self._require_paged("decode_step_paged")
        return self._impl.decode_step_paged(self.cfg, params, tokens, pool,
                                            table, lengths, offsets,
                                            store=store, kernel=kernel,
                                            return_stats=return_stats)

    def prefill_chunk(self, params, tokens, cache, store=None,
                      start_pos=0, chunk_len=None,
                      kernel: Optional[str] = None,
                      return_stats: bool = False):
        self._require_paged("prefill_chunk")
        return self._impl.prefill_chunk(self.cfg, params, tokens, cache,
                                        store=store, start_pos=start_pos,
                                        chunk_len=chunk_len, kernel=kernel,
                                        return_stats=return_stats)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
