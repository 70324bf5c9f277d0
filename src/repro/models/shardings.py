"""Logical-axis -> mesh-axis resolution (DESIGN.md §5).

Param layout: Megatron-style tensor parallelism on ``model`` (heads /
d_ff / vocab / d_inner), plus FSDP-style sharding of the remaining large
dim over ``data`` for cfg.fsdp archs (XLA inserts the per-layer
all-gathers inside the unit scan).  Multi-pod: params are REPLICATED over
``pod`` — each pod is a federation silo (the MAFL view), aggregation
collectives cross pods.

Every rule checks divisibility; non-divisible dims stay replicated (e.g.
whisper's 20 heads on a 16-way model axis).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, InputShape

# logical axis -> candidate mesh axis (in priority order per-leaf)
_MODEL_AXES = ("vocab", "ff", "dinner", "heads", "kv_heads", "experts")
_FSDP_AXES = ("embed", "experts", "ff")  # first divisible one gets 'data'


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def resolve_leaf_spec(
    cfg: ArchConfig,
    axes: Tuple[Optional[str], ...],
    shape: Tuple[int, ...],
    mesh: Mesh,
    policy: str = "baseline",
    zero1: bool = False,
) -> P:
    """Greedy left-to-right assignment of mesh axes to one param leaf.

    Policies (§Perf iterations — EXPERIMENTS.md):
      baseline  — model TP on the first divisible model-axis dim, FSDP
                  'data' on the first _FSDP_AXES dim (often the
                  CONTRACTING 'embed' dim — XLA then partial-sums and
                  all-reduces ACTIVATIONS, which the roofline exposed as
                  the grok 9 TB/step pathology);
      gather2d  — never put 'data' on a contracting dim: the ff/d_inner
                  output dim is sharded over ('model','data') jointly
                  when divisible, so weights are fully sharded but every
                  contraction stays local (weight-gather, not
                  activation-all-reduce).
    zero1       — for OPTIMIZER state only: additionally shard the first
                  divisible dim over 'data' (elementwise update; no
                  contraction constraints).
    """
    model_n = _axis_size(mesh, "model")
    data_n = _axis_size(mesh, "data")
    out: list = [None] * len(shape)
    used = set()

    # pass 1: tensor parallelism on 'model' (optionally joint with data)
    for i, (ax, dim) in enumerate(zip(axes, shape)):
        if "model" in used:
            break
        if ax in _MODEL_AXES and ax != "experts" and model_n > 1 and dim % model_n == 0:
            if (
                policy == "gather2d"
                and cfg.fsdp
                and ax in ("ff", "dinner", "vocab")
                and data_n > 1
                and dim % (model_n * data_n) == 0
            ):
                out[i] = ("model", "data")
                used.update(("model", "data"))
            else:
                out[i] = "model"
                used.add("model")
    # pass 2: FSDP on 'data'
    if cfg.fsdp and data_n > 1 and "data" not in used and policy == "baseline":
        for i, (ax, dim) in enumerate(zip(axes, shape)):
            if out[i] is None and ax in _FSDP_AXES and dim % data_n == 0:
                out[i] = "data"
                used.add("data")
                break
    # pass 3: ZeRO-1 (optimizer state only): any divisible dim takes 'data'
    if zero1 and data_n > 1 and "data" not in used:
        for i, (ax, dim) in enumerate(zip(axes, shape)):
            if ax == "layers":
                continue  # never shard the scan dim
            if out[i] is None and dim % data_n == 0 and dim >= data_n:
                out[i] = "data"
                used.add("data")
                break
    return P(*out)


# §Perf iteration "fsdp-gather": before each use, constrain FSDP-sharded
# weights to their model-only layout.  XLA then all-gathers the (small,
# bf16) WEIGHT over 'data' instead of partial-summing and all-reducing
# the (large, f32) activations — the grok 9 TB/step pathology fix.
FSDP_WEIGHT_GATHER = False


def set_fsdp_weight_gather(value: bool) -> None:
    global FSDP_WEIGHT_GATHER
    FSDP_WEIGHT_GATHER = value


def constrain_group_dim(x):
    """Pin dim 0 of a [G, ...] dispatch tensor to the data-parallel axes —
    reshapes from [B, S, ...] can silently drop the batch sharding, after
    which XLA replicates the whole MoE dispatch (observed as 51 GB/layer
    hidden-state all-gathers on grok).  No-op outside a mesh context."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if not dp or x.shape[0] % int(np.prod([mesh.shape[a] for a in dp])) != 0:
        return x
    return jax.lax.with_sharding_constraint(x, P(dp, *([None] * (x.ndim - 1))))


def constrain_microbatch(x):
    """Pin dim 1 of an [accum, B/accum, ...] microbatch stack to the
    data-parallel axes (the reshape from [B, ...] can drop the batch
    sharding, replicating every microbatch's activations)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or x.ndim < 2:
        return x
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if not dp or x.shape[1] % int(np.prod([mesh.shape[a] for a in dp])) != 0:
        return x
    return jax.lax.with_sharding_constraint(x, P(None, dp, *([None] * (x.ndim - 2))))


def maybe_gather_weight(w, axes: Tuple[Optional[str], ...]):
    """Apply a model-only sharding constraint to a weight (strips 'data')."""
    if not FSDP_WEIGHT_GATHER:
        return w
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or "model" not in mesh.shape:
        return w
    model_n = mesh.shape["model"]
    out = [None] * w.ndim
    for i, (ax, dim) in enumerate(zip(axes, w.shape)):
        if ax in _MODEL_AXES and ax != "experts" and model_n > 1 and dim % model_n == 0:
            out[i] = "model"
            break
    return jax.lax.with_sharding_constraint(w, P(*out))


def param_specs(
    cfg: ArchConfig, shapes: Any, axes: Any, mesh: Mesh,
    policy: str = "baseline", zero1: bool = False,
) -> Any:
    """PartitionSpec tree mirroring the param tree."""

    def is_axes_leaf(x):
        return isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)

    flat_shapes, treedef = jax.tree.flatten(shapes)
    flat_axes = jax.tree.flatten(axes, is_leaf=is_axes_leaf)[0]
    assert len(flat_shapes) == len(flat_axes), (len(flat_shapes), len(flat_axes))
    specs = [
        resolve_leaf_spec(cfg, ax, tuple(s.shape), mesh, policy=policy, zero1=zero1)
        for s, ax in zip(flat_shapes, flat_axes)
    ]
    return jax.tree.unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# Activation / input sharding
# ---------------------------------------------------------------------------


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _dp_total(mesh: Mesh) -> int:
    return int(np.prod([_axis_size(mesh, a) for a in batch_axes(mesh)]))


def input_spec_tree(cfg: ArchConfig, shape: InputShape, specs_in: Any, mesh: Mesh) -> Any:
    """PartitionSpecs for the input_specs() stand-ins.

    Batch-shardable inputs go over (pod, data); small-batch decode state
    shards its largest dim over ('data','model') instead (sequence-
    sharded KV — DESIGN.md §5 long-context decode).
    """
    dp = _dp_total(mesh)
    ba = batch_axes(mesh)
    model_n = _axis_size(mesh, "model")
    data_n = _axis_size(mesh, "data")

    def token_like(s) -> P:
        if s.shape[0] % dp == 0 and dp > 1:
            return P(ba, *([None] * (len(s.shape) - 1)))
        return P(*([None] * len(s.shape)))

    def state_leaf(s) -> P:
        # leaves look like [R(scan), B, ...] — never shard R (dim 0)
        dims = list(s.shape)
        out: list = [None] * len(dims)
        if len(dims) >= 2 and dims[1] == shape.global_batch and dims[1] % dp == 0 and dp > 1:
            out[1] = ba
            # additionally shard the largest remaining dim over 'model'
            rest = [(d, i) for i, d in enumerate(dims[2:], start=2)]
            if rest:
                d, i = max(rest)
                if d % model_n == 0 and model_n > 1 and d >= model_n * 8:
                    out[i] = "model"
            return P(*out)
        # tiny batch (long_500k): shard the largest dim over (data, model)
        rest = [(d, i) for i, d in enumerate(dims[1:], start=1)]
        if rest:
            d, i = max(rest)
            if d % (data_n * model_n) == 0 and d >= data_n * model_n * 8:
                out[i] = ("data", "model")
            elif d % data_n == 0 and data_n > 1 and d >= data_n * 8:
                out[i] = "data"
            elif d % model_n == 0 and model_n > 1 and d >= model_n * 8:
                out[i] = "model"
        return P(*out)

    def assign(path_leaf):
        return path_leaf  # placeholder (tree built below)

    out: Dict[str, Any] = {}
    for key, val in specs_in.items():
        if key in ("tokens", "token", "prefix", "frames"):
            out[key] = token_like(val)
        elif key == "state":
            out[key] = jax.tree.map(state_leaf, val)
        else:
            raise KeyError(key)
    return out


def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
