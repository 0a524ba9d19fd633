"""Logical-axis sharding with divisibility-aware fallbacks.

Params and activations are annotated with *logical* axis names; a rules
table maps each logical name to an ordered list of physical mesh-axis
candidates. At spec-resolution time we pick, per tensor dimension, the
first candidate whose size divides the dimension and which is not
already used by another dimension of the same tensor. This is what lets
one rule set cover qwen2.5 (40 heads — not divisible by 16 → falls back
to sharding head_dim) and smollm (9 heads) alongside the cleanly
divisible archs.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# Logical axis name -> ordered physical candidates. "data" expands to all
# pure-DP axes present in the mesh (pod + data).
DEFAULT_RULES: dict[str, Tuple[str, ...]] = {
    "batch": ("dp",),            # activation batch: pod+data combined
    "seq": (),                   # unsharded by default
    "longseq": ("dp", "model"),  # long-context KV/sequence sharding
    "cache_seq": ("model",),     # decode KV-cache sequence dim
    "vocab": ("model",),
    "embed": (),                 # d_model dim of params: replicated (TP = megatron)
    "fsdp_embed": ("data",),     # d_model dim, optimizer-state/fsdp sharding
    "mlp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": ("model",),      # used as fallback when heads don't divide
    "qkv": ("model",),           # fused q/k/v output dim
    "expert": ("model",),
    "expert_mlp": ("model",),    # fallback: shard inside-expert d_ff
    "layers": (),                # stacked-scan leading dim: never sharded
    "state": (),                 # SSM state dims
    "dconv": (),
    "table_d": (),               # embed/lm-head d_model dim: never sharded
    "seq_shard": ("model",),     # saved-activation sequence sharding (SP)
    # serve-plane camera lanes (repro.core.fleet): per-camera session
    # state is embarrassingly parallel, so the leading C dim shards over
    # a dedicated "camera" mesh axis, or rides a pure-DP axis when the
    # fleet shares a training mesh
    "camera": ("camera", "data", "dp"),
}


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Metadata for a single parameter tensor."""
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]       # logical axis name per dim
    init: str = "normal"                  # normal | zeros | ones | small_normal
    dtype: str = "float32"
    scale: Optional[float] = None         # stddev override

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def auto_mesh(shape: Sequence[int], names: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with Auto axes, so shardings propagate through
    eager ops and ``jit`` without per-op ``out_sharding`` annotations
    (``make_mesh`` alone makes Explicit axes)."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(shape))


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def spec_leaves(tree):
    return jax.tree_util.tree_leaves(tree, is_leaf=is_spec)


def tree_map_specs(fn, tree, *rest):
    return jax.tree_util.tree_map(fn, tree, *rest, is_leaf=is_spec)


def num_params(spec_tree) -> int:
    return int(sum(np.prod(s.shape) for s in spec_leaves(spec_tree)))


# ---------------------------------------------------------------------------
# Spec resolution
# ---------------------------------------------------------------------------

def _dp_axes(mesh_axes: Sequence[str]) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh_axes)


def resolve_axis(logical: Optional[str], dim: int, mesh: Mesh,
                 used: set, rules=None):
    """Pick physical sharding (axis name, tuple of names, or None) for one dim."""
    if logical is None:
        return None
    rules = rules or DEFAULT_RULES
    candidates = rules.get(logical, ())
    for cand in candidates:
        if cand == "dp":
            axes = tuple(a for a in _dp_axes(mesh.axis_names) if a not in used)
            if not axes:
                continue
            size = int(np.prod([mesh.shape[a] for a in axes]))
            if dim % size == 0:
                used.update(axes)
                return axes if len(axes) > 1 else axes[0]
            # try the largest single dp axis
            for a in axes:
                if dim % mesh.shape[a] == 0:
                    used.add(a)
                    return a
        else:
            if cand in mesh.axis_names and cand not in used and dim % mesh.shape[cand] == 0:
                used.add(cand)
                return cand
    return None


def partition_spec(axes: Sequence[Optional[str]], shape: Sequence[int],
                   mesh: Mesh, rules=None) -> P:
    used: set = set()
    out = []
    for logical, dim in zip(axes, shape):
        out.append(resolve_axis(logical, dim, mesh, used, rules))
    # strip trailing Nones for tidiness
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def spec_partition_specs(spec_tree, mesh: Mesh, rules=None):
    return tree_map_specs(
        lambda s: partition_spec(s.axes, s.shape, mesh, rules), spec_tree)


def spec_shardings(spec_tree, mesh: Mesh, rules=None):
    return tree_map_specs(
        lambda s: NamedSharding(mesh, partition_spec(s.axes, s.shape, mesh, rules)),
        spec_tree)


def spec_shapes(spec_tree, dtype_override=None):
    return tree_map_specs(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype_override or s.dtype),
        spec_tree)


# ---------------------------------------------------------------------------
# Materialization
# ---------------------------------------------------------------------------

def _init_one(spec: ParamSpec, key):
    import jax.numpy as jnp
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "neg_ssm_a":
        # A_log init for SSM blocks: A = -exp(A_log) in [-16, -1)
        return jnp.log(jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0)).astype(spec.dtype)
    fan_in = spec.shape[-1] if len(spec.shape) >= 2 else spec.shape[0]
    std = spec.scale if spec.scale is not None else (1.0 / np.sqrt(max(1, fan_in)))
    if spec.init == "small_normal":
        std = 0.02
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(spec.dtype)


def materialize(spec_tree, key):
    """Instantiate a spec tree into arrays with per-leaf folded keys."""
    leaves, treedef = jax.tree_util.tree_flatten(spec_tree, is_leaf=is_spec)
    keys = jax.random.split(key, max(1, len(leaves)))
    arrays = [_init_one(s, k) for s, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, arrays)


# ---------------------------------------------------------------------------
# Activation sharding constraints (no-op outside a mesh context)
# ---------------------------------------------------------------------------

def constrain(x, *axes, rules=None):
    """with_sharding_constraint by logical axes; silently no-op when the
    surrounding mesh lacks the axes (single-device tests)."""
    mesh = _current_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = partition_spec(axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _current_mesh():
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m
