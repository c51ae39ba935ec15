"""The system under test for dense-decoder configurations: the repo's
``ServingEngine`` (``src/repro/serving/engine.py``) driven through its
public loop, ``register_corpus`` once and then ``submit`` / ``run``.

The engine gets the configuration's model and its deployment's
``max_slots`` and ``max_seq``; every other engine setting stays at its
default, so a change of a default is measured as users get it.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from chipbench import corpus as corpus_lib
from chipbench import weights as W

CORPUS_ID = "corpus"


def model_config(conf: dict):
    """The program's ``ModelConfig`` for ``conf``, checked key by key."""
    from repro.configs import get_config
    base = get_config(conf["repro_arch"])
    m = W.dims(conf)
    if conf["hidden_act"] != "silu":
        raise ValueError(f"{conf['name']}: the dense path is SwiGLU (silu)")
    window = (conf.get("sliding_window") or 0
              if conf.get("use_sliding_window", True) else 0)
    mo = conf["moska"]
    cfg = dataclasses.replace(
        base, num_layers=m["L"], d_model=m["d"], num_heads=m["H"],
        num_kv_heads=m["KH"], head_dim=m["D"], d_ff=m["F"],
        vocab_size=m["V"], qkv_bias=m["bias"],
        rope_theta=float(conf["rope_theta"]),
        rms_eps=float(conf["rms_norm_eps"]), tie_embeddings=m["tied"],
        dtype=conf["torch_dtype"], attn_window=int(window),
        moska=dataclasses.replace(
            base.moska, chunk_size=mo["chunk_size"],
            top_k_chunks=mo["top_k_chunks"],
            query_capacity_factor=mo["query_capacity_factor"]))
    if cfg.family != "dense" or cfg.moe.enabled:
        raise ValueError(f"{conf['name']}: not a dense configuration")
    return cfg


def program_params(conf: dict, seed: int):
    """The benchmark's weights in the program's parameter layout."""
    m = W.dims(conf)
    lw, gw = W.stacked(seed, m, conf["torch_dtype"])
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in lw}
    params = {
        "embed": {"embed": gw["embed"]},
        "layers": {"ln1": {"scale": lw["input_norm"]},
                   "ln2": {"scale": lw["post_norm"]},
                   "attn": attn,
                   "mlp": {k: lw[k] for k in ("w_gate", "w_up", "w_down")}},
        "final_norm": {"scale": gw["final_norm"]},
    }
    if not m["tied"]:
        params["unembed"] = {"unembed": gw["unembed"]}
    return params


class Server:
    """One engine with its weights and, where the traffic has one, its
    registered corpus."""

    def __init__(self, conf: dict, traffic: dict, seed: int):
        import jax
        from repro import obs
        from repro.serving.engine import EngineConfig, ServingEngine
        obs.reset_registry()
        self._obs = obs
        cfg = model_config(conf)
        params = jax.block_until_ready(program_params(conf, seed))
        self.engine = ServingEngine(cfg, params, EngineConfig(
            max_slots=traffic["max_slots"], max_seq=traffic["max_seq"]))
        self.vocab_size = cfg.vocab_size
        self.corpus = np.zeros((0,), np.int32)
        self.corpus_id: Optional[str] = None
        n = traffic.get("corpus_tokens", 0)
        if n:
            C = cfg.moska.chunk_size
            toks = corpus_lib.corpus_tokens(n // C * C, cfg.vocab_size, seed)
            self.engine.register_corpus(CORPUS_ID, toks)
            jax.block_until_ready(self.engine.stores[CORPUS_ID])
            self.corpus, self.corpus_id = toks, CORPUS_ID

    # -- the loop ------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int):
        """Queue one request; returns its handle, whose ``generated`` list
        grows as tokens are produced and whose ``done`` turns true."""
        q = self.engine.scheduler.queue
        uid = self.engine.submit(prompt.tolist(), int(max_new_tokens),
                                 corpus_id=self.corpus_id)
        req = q[-1]
        assert req.uid == uid
        return req

    def set_wave_hook(self, fn) -> None:
        """``fn()`` runs at the end of every decode wave, inside the
        engine's loop; it may submit requests, and may raise to leave it."""
        self.engine.wave_hooks = [fn]

    def serve(self) -> None:
        """Run the engine's loop until it has nothing left to do (or the
        wave hook raises). The loop is entered only when nothing is in a
        slot: ``run()`` starts every call with a fresh token vector, so a
        call that resumed live slots would feed them token 0."""
        self.engine.run()

    @property
    def busy(self) -> bool:
        return not self.engine.scheduler.idle

    def prefill_lengths(self, lo: int, hi: int) -> List[int]:
        """One prompt length per prefill program that prompts of ``lo`` to
        ``hi`` tokens can reach."""
        buckets = self.engine.prefill_buckets
        if not buckets:
            return list(range(lo, hi + 1))
        out = []
        for b in buckets:
            prev = max([x for x in buckets if x < b], default=0)
            if b >= lo and prev < hi:
                out.append(min(b, hi))
        return out

    def counters(self) -> dict:
        return self._obs.get_registry().snapshot()

    def close(self) -> None:
        self.engine = None
