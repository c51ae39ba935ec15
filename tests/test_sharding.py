"""Sharding-rule resolution: divisibility guard, axis-conflict avoidance,
variant application, param pspec mapping."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_host_mesh
from repro.sharding import specs as sp


MESH_AXES = ("data", "model")
SIZES = {"data": 16, "model": 16}


def _resolve(rules, names, shape):
    return sp._resolve(rules, names, MESH_AXES, shape, SIZES)


def test_divisibility_guard_drops_nondividing_axis():
    rules = {"kv_heads": "model", "batch": "data"}
    # 8 kv heads cannot shard over model=16 -> replicated
    assert _resolve(rules, ("batch", "kv_heads"), (128, 8)) == P("data", None)
    # 16 kv heads can
    assert _resolve(rules, ("batch", "kv_heads"), (128, 16)) == \
        P("data", "model")


def test_axis_used_once():
    rules = {"a": "model", "b": "model"}
    # the second request for "model" must be dropped, not duplicated
    assert _resolve(rules, ("a", "b"), (32, 32)) == P("model", None)


def test_tuple_axes_partial_divisibility():
    rules = {"batch": ("pod", "data")}
    # no 'pod' axis in this mesh: falls back to data alone
    assert _resolve(rules, ("batch",), (32,)) == P("data")


def test_apply_variant_overrides():
    rules = sp.apply_variant(sp.SERVE_RULES, "weights_resident")
    assert rules["p_dm"] is None
    assert sp.SERVE_RULES["p_dm"] == "data"  # original untouched
    both = sp.apply_variant(sp.TRAIN_RULES, "seqpar")
    assert both["seq_res"] == "model"


def test_param_pspecs_name_mapping():
    mesh = make_host_mesh()
    params = {
        "layers": {
            "attn": {"wq": jnp.zeros((4, 64, 128))},   # stacked (L, d, h)
            "mlp": {"w_down": jnp.zeros((4, 128, 64))},
        },
        "embed": {"embed": jnp.zeros((1000, 64))},
        "final_norm": {"scale": jnp.zeros((64,))},
    }
    specs = sp.param_pspecs(params, sp.TRAIN_RULES, mesh)
    # leading scan dim maps to None; named dims resolved (mesh size 1 so
    # everything divisible)
    assert specs["layers"]["attn"]["wq"] == P(None, "data", "model")
    assert specs["layers"]["mlp"]["w_down"] == P(None, "model", "data")
    assert specs["embed"]["embed"] == P("model", None)
    assert specs["final_norm"]["scale"] == P(None)


def test_lsc_identity_without_rules():
    sp.set_rules(None)
    x = jnp.ones((4, 4))
    assert sp.lsc(x, "batch", "d_model") is x


def test_lsc_rank_alignment():
    """Names align from the right when rank differs (decode drops seq)."""
    with jax.set_mesh(make_host_mesh()):
        sp.set_rules({"d_ff": "data"})
        try:
            x = jnp.ones((2, 8))
            y = sp.lsc(x, None, None, "d_ff")  # 3 names, rank 2
            assert y.shape == x.shape
        finally:
            sp.set_rules(None)
