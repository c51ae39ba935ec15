"""Logical-axis sharding rules (t5x-style) for the MoSKA framework.

Model code annotates activations with *logical* axis names via ``lsc(x,
"batch", "seq", "heads", ...)``. Launch code installs a rule set mapping
logical names to mesh axes; with no rules installed (unit tests, CPU smoke)
``lsc`` is the identity, so model code never needs a mesh to run.

Rule sets
---------
``TRAIN_RULES``    FSDP + TP: batch over (pod, data); parameter dim-0 /
                   d_model over data (fully-sharded); heads / d_ff / vocab /
                   experts over model.
``SERVE_RULES``    inference: batch over (pod, data); params replicated over
                   data, TP over model; shared KV *chunks* over data (the
                   paper's Shared-KV-node pool); unique KV batch-sharded
                   (the Unique-KV-node pool).
``LONGCTX_RULES``  batch=1 decode: context/chunk parallelism — chunks over
                   (pod, data).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]
LogicalRules = Dict[str, AxisVal]

_state = threading.local()


def set_rules(rules: Optional[LogicalRules]) -> None:
    _state.rules = rules


def current_rules() -> Optional[LogicalRules]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[LogicalRules]):
    prev = current_rules()
    set_rules(rules)
    try:
        yield
    finally:
        set_rules(prev)


def _resolve(rules: LogicalRules, names: Sequence[Optional[str]],
             mesh_axes: Sequence[str],
             shape: Optional[Sequence[int]] = None,
             axis_sizes: Optional[Dict[str, int]] = None) -> P:
    """Resolve logical names to mesh axes; with ``shape`` given, drop any
    axis whose size does not divide the dimension (e.g. 8 kv heads cannot
    shard over model=16 — replicate instead)."""
    out = []
    used: set = set()
    for i, n in enumerate(names):
        if n is None:
            out.append(None)
            continue
        ax = rules.get(n)
        if ax is None:
            out.append(None)
            continue
        cand = tuple(a for a in (ax if isinstance(ax, tuple) else (ax,))
                     if a in mesh_axes and a not in used)
        if shape is not None and axis_sizes is not None:
            kept = []
            size = 1
            for a in cand:
                if shape[i] % (size * axis_sizes[a]) == 0:
                    kept.append(a)
                    size *= axis_sizes[a]
            cand = tuple(kept)
        used.update(cand)
        if not cand:
            out.append(None)
        elif len(cand) == 1:
            out.append(cand[0])
        else:
            out.append(cand)
    return P(*out)


def spec(names: Sequence[Optional[str]],
         rules: Optional[LogicalRules] = None,
         mesh: Optional[jax.sharding.Mesh] = None) -> P:
    """Resolve logical names to a PartitionSpec under the current rules."""
    rules = rules if rules is not None else current_rules()
    if rules is None:
        return P()
    if mesh is None:
        mesh = _current_mesh()
    axes = mesh.axis_names if mesh is not None else ()
    return _resolve(rules, names, axes)


def _current_mesh() -> Optional[jax.sharding.AbstractMesh]:
    """The mesh installed with ``jax.set_mesh``, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def logical_sharding_constraint(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names; identity unless rules
    are set and a mesh is installed with ``jax.set_mesh``."""
    rules = current_rules()
    if rules is None:
        return x
    mesh = _current_mesh()
    if mesh is None:
        return x
    # align names to rank from the right (decode drops leading seq dims)
    if len(names) > x.ndim:
        names = names[len(names) - x.ndim:]
    elif len(names) < x.ndim:
        names = (None,) * (x.ndim - len(names)) + tuple(names)
    ps = _resolve(rules, names, mesh.axis_names, x.shape, dict(mesh.shape))
    return jax.lax.with_sharding_constraint(x, ps)


lsc = logical_sharding_constraint


# ---------------------------------------------------------------------------
# Rule sets
# ---------------------------------------------------------------------------

TRAIN_RULES: LogicalRules = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_res": None,            # residual-stream seq dim (seqpar variant)
    "kv_seq": None,
    "chunk_seq": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "d_model": None,            # activations keep d_model replicated
    "d_ff": "model",
    "vocab": "model",
    "experts": "model",
    "expert_cap": None,
    "expert_dm": None,
    "chunks": "data",
    "state": "model",
    # parameter logical dims
    "p_dm": "data",             # FSDP: weight d_model dim over data
    "p_heads": "model",
    "p_ff": "model",
    "p_vocab": "model",
    "p_experts": "model",
    "p_inner": "model",
}

SERVE_RULES: LogicalRules = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_res": None,
    # KV caches / chunk stores shard their *sequence/content* dim over the
    # model axis (flash-decoding KV split): GQA kv_heads (often 8 or 1)
    # cannot shard over model=16, but seq always divides.
    "kv_seq": "model",
    "chunk_seq": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "d_model": None,
    "d_ff": "model",
    "vocab": "model",
    "experts": "model",
    "expert_cap": None,
    "expert_dm": None,
    # shared KV chunk pool over (pod, data) = the Shared-KV node pool;
    # single-pod meshes resolve this to plain data. Replicating per pod
    # instead makes multi-pod XLA re-gather the store every layer (37x
    # collective regression — EXPERIMENTS §Perf multi-pod iteration).
    "chunks": ("pod", "data"),
    "state": "model",
    # weight-stationary serving does not fit >100B models on 16GB chips:
    # serve also shards the d_model weight dim over data (per-layer
    # all-gather inside the scan; see EXPERIMENTS.md §Perf for the cost)
    "p_dm": "data",
    "p_heads": "model",
    "p_ff": "model",
    "p_vocab": "model",
    "p_experts": "model",
    "p_inner": "model",
}

LONGCTX_RULES: LogicalRules = {
    **SERVE_RULES,
    "batch": None,              # batch=1: cannot shard
    "chunks": ("pod", "data"),  # context parallelism over chunks
}


# ---------------------------------------------------------------------------
# §Perf hillclimb variants: named rule overrides applied on top of the
# baseline rule set by launch/dryrun.py --variant <name>. Each encodes one
# hypothesis from EXPERIMENTS.md §Perf.
# ---------------------------------------------------------------------------

VARIANTS: Dict[str, LogicalRules] = {
    # decode: keep weights resident (TP over model only) instead of
    # FSDP-gathering every layer's weights each step — trades per-chip
    # weight memory for zero weight all-gather traffic.
    "weights_resident": {"p_dm": None},
    # MoE decode: experts resident over the *data* axis, expert weight
    # matrices TP-sharded over model — removes the per-layer expert-weight
    # all-gather; dispatch all-to-all routes activations instead.
    "expert_resident": {"p_experts": "data", "experts": "data",
                        "p_dm": "model", "expert_dm": "model"},
    # train: sequence-parallel residual stream — the scan carry (and thus
    # the per-layer saved activation for backward) is sharded over model;
    # attention/FFN re-gather, adding collectives but dividing the dominant
    # activation memory by the model-axis size.
    "seqpar": {"seq_res": "model"},
    # train: combine seqpar with kv_seq sharding of fresh K/V (prefill)
    "seqpar+kv": {"seq_res": "model", "kv_seq": "model"},
    # train: FSDP on the *model-sharded* weight dim instead of d_model —
    # the weight-grad einsum then has the natural partial-over-data ->
    # reduce-scatter strategy (output dim already carries the data axis),
    # instead of gathering global-batch activations (§Perf, mistral it. 3)
    # multi-pod decode: shard the chunk pool over (pod, data) — each pod
    # owns half the chunks (true two-pool disagg) instead of replicating
    # the store per pod and re-gathering it
    "chunks_global": {"chunks": ("pod", "data")},
    "fsdp2": {"p_dm": None,
              "p_ff": ("model", "data"),
              "p_heads": ("model", "data"),
              "p_vocab": ("model", "data"),
              "p_inner": ("model", "data")},
}


def apply_variant(rules: LogicalRules, variant: Optional[str]
                  ) -> LogicalRules:
    if not variant:
        return rules
    out = dict(rules)
    for key in variant.split(","):
        out.update(VARIANTS[key])
    return out


# ---------------------------------------------------------------------------
# Parameter PartitionSpecs
# ---------------------------------------------------------------------------

# Map param leaf names -> logical dim names. Leading scan (layer-stack) dims
# are detected by rank mismatch and mapped to None.
_PARAM_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    "embed": ("p_vocab", None),
    "unembed": ("p_vocab", None),
    "wq": ("p_dm", "p_heads"),
    "wk": ("p_dm", "p_heads"),
    "wv": ("p_dm", "p_heads"),
    "wo": ("p_heads", "p_dm"),
    "bq": ("p_heads",),
    "bk": ("p_heads",),
    "bv": ("p_heads",),
    "w_gate": ("p_dm", "p_ff"),
    "w_up": ("p_dm", "p_ff"),
    "w_down": ("p_ff", "p_dm"),
    "router": ("p_dm", None),
    # experts over model axis (expert parallel); per-expert mats FSDP over
    # data on the d_model dim. d_ff stays local (per-expert FFNs are small).
    "e_gate": ("p_experts", "p_dm", None),
    "e_up": ("p_experts", "p_dm", None),
    "e_down": ("p_experts", None, "p_dm"),
    "scale": (None,),
    "bias": (None,),
    "in_proj": ("p_dm", "p_inner"),
    "out_proj": ("p_inner", "p_dm"),
    "conv_w": (None, "p_inner"),
    "conv_b": ("p_inner",),
    "a_log": ("p_inner",),
    "d_skip": ("p_inner",),
    "dt_bias": ("p_inner",),
    "lru_in": ("p_dm", "p_inner"),
    "lru_out": ("p_inner", "p_dm"),
    "lru_a": ("p_inner",),
    "lru_gate_w": (None, "p_inner"),
    "lru_gate_b": ("p_inner",),
    "pos_embed": (None, None),
}


def param_pspecs(params, rules: LogicalRules, mesh: jax.sharding.Mesh):
    """Build a pytree of PartitionSpec matching ``params``.

    Leaf names are resolved from the last path element; unknown names are
    replicated. Extra leading dims (layer-stack from vmap'd init) map to None.
    """
    axes = mesh.axis_names
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(path, leaf):
        name = None
        for p in reversed(path):
            if hasattr(p, "key"):
                name = p.key
                break
        dims = _PARAM_AXES.get(name)
        if dims is None:
            return P()
        pad = leaf.ndim - len(dims)
        names = (None,) * pad + tuple(dims)
        return _resolve(rules, names, axes, leaf.shape, sizes)

    return jax.tree_util.tree_map_with_path(one, params)


def named_sharding_tree(params, rules: LogicalRules, mesh: jax.sharding.Mesh):
    specs = param_pspecs(params, rules, mesh)
    return jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
