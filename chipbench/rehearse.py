#!/usr/bin/env python3
"""Compile a cell's programs at their real sizes for a described TPU v5e,
without a chip, and print each one's ``memory_analysis()`` and compile time.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload <cell>

Covers the weights' creation, the corpus registration (one prefill of the
whole corpus), the decode step and the largest prefill bucket the mix's
prompts reach, each with the engine's defaults (host-callback metrics on).
Nothing runs, so nothing here is a time or a speed on the chip.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import run
    from chipbench.systems import repro_dense as S
    from repro import obs
    from repro.serving.engine import EngineConfig, ServingEngine
    jax.config.update("jax_enable_compilation_cache", False)

    cell = run.load_cell(args.workload)
    conf, mix = cell.conf, cell.mix
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def report(name, fn, *a, **kw):
        t = time.perf_counter()
        c = jax.jit(fn, **kw).lower(*a).compile()
        ma = c.memory_analysis()
        tot = (ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print(f"{args.workload} {name}: compile {time.perf_counter() - t:.1f}"
              f" s; arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, "
              f"outputs {ma.output_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{ma.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{ma.alias_size_in_bytes / 1e9:.3f} GB; total "
              f"{tot / 1e9:.3f} GB", flush=True)
        return c

    cfg = S.model_config(conf)
    report("weights", lambda: S.program_params(conf, 0))
    params = sds(jax.eval_shape(lambda: S.program_params(conf, 0)))
    obs.enable_jit_metrics(True)
    eng = ServingEngine(cfg, params, EngineConfig(
        max_slots=mix["max_slots"], max_seq=mix["max_seq"]))
    model = eng.model
    C = cfg.moska.chunk_size
    N = mix.get("corpus_tokens", 0) // C * C
    store = None
    if N:
        toks = jax.ShapeDtypeStruct((1, N), jnp.int32, sharding=one)

        def register(p, t):
            cache = model.init_cache(1, N, jnp.bfloat16)
            _, cache = model.prefill(p, t, cache)
            from repro.core.shared_kv import build_store
            return build_store(cache.k[:, 0], cache.v[:, 0], C)
        report("corpus registration", register, params, toks)
        store = sds(jax.eval_shape(register, params, toks))
    use = store is not None
    cache = sds(jax.eval_shape(lambda: model.init_cache(
        mix["max_slots"], mix["max_seq"], jnp.bfloat16)))
    tok_b = jax.ShapeDtypeStruct((mix["max_slots"],), jnp.int32,
                                 sharding=one)
    t = time.perf_counter()
    c = eng._decode.lower(params, tok_b, cache, store,
                          use_store=use).compile()
    ma = c.memory_analysis()
    print(f"{args.workload} decode step: compile "
          f"{time.perf_counter() - t:.1f} s; arguments "
          f"{ma.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{ma.temp_size_in_bytes / 1e9:.3f} GB, aliased "
          f"{ma.alias_size_in_bytes / 1e9:.3f} GB", flush=True)
    hi = mix["prompt_len"]["max"]
    from repro.serving.engine import bucket_for
    b = bucket_for(eng.prefill_buckets, hi)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    t = time.perf_counter()
    c = eng._prefill.lower(params, jax.ShapeDtypeStruct(
        (1, b), jnp.int32, sharding=one), scalar, scalar, store,
        use_store=use).compile()
    ma = c.memory_analysis()
    print(f"{args.workload} prefill bucket {b}: compile "
          f"{time.perf_counter() - t:.1f} s; arguments "
          f"{ma.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{ma.temp_size_in_bytes / 1e9:.3f} GB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
