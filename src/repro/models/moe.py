"""Mixture-of-Experts FFN (grok-1, jamba, llama4-scout).

Dispatch is sort-based (dropless-ish with a static capacity): tokens are
flattened, their top-k expert choices sorted by expert id, and each
expert processes a static [capacity] slice — no [tokens, experts,
capacity] one-hot tensors, so 32k-sequence prefill stays feasible.
Overflowing tokens are dropped (standard capacity-factor semantics) and
the auxiliary load-balance loss (Switch-style) discourages overflow.

Sharding: expert matrices are [E, d, ff] with ff on the ``model`` axis
(tensor-parallel experts) and, under FSDP, E or d on ``data``.  An
all-to-all expert-parallel layout is a recorded §Perf iteration.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import make_param, pdtype
from repro.models.shardings import maybe_gather_weight as _mg


def init_moe(cfg: ArchConfig, key) -> Tuple[Dict, Dict]:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = pdtype(cfg)
    ks = jax.random.split(key, 4)
    params = {
        "router": make_param(ks[0], (d, E), jnp.float32),
        "w_gate": make_param(ks[1], (E, d, ff), dt, fan_in=d),
        "w_up": make_param(ks[2], (E, d, ff), dt, fan_in=d),
        "w_down": make_param(ks[3], (E, ff, d), dt, fan_in=ff),
    }
    axes = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "ff"),
        "w_up": ("experts", "embed", "ff"),
        "w_down": ("experts", "ff", "embed"),
    }
    return params, axes


# §Perf iteration "grouped dispatch": sorting ALL tokens globally forces
# XLA to move batch-sharded tokens across devices (the grok dispatch
# all-reduces).  With G == the data-parallel group count, every sort /
# gather / scatter below is LOCAL to a device group (leading dim G is
# batch-sharded), and only the expert matmuls touch the network (weight
# gathers).  G=1 reproduces the baseline global dispatch.
DISPATCH_GROUPS = 1


def set_dispatch_groups(value: int) -> None:
    global DISPATCH_GROUPS
    DISPATCH_GROUPS = value


def apply_moe(cfg: ArchConfig, p: Dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (out [B, S, d], aux load-balance loss scalar).

    With DISPATCH_GROUPS > 1 the dispatch runs under a PARTIAL shard_map
    over the data-parallel axes: sort/gather/scatter are forced device-
    local (XLA's auto-partitioner otherwise replicates the expert buffers
    — observed as 193 GB/layer all-gathers on grok), while the expert
    matmuls stay in auto mode so the model-axis tensor parallelism is
    unchanged.
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    N = B * S
    G = DISPATCH_GROUPS if (DISPATCH_GROUPS > 1 and N % DISPATCH_GROUPS == 0) else 1
    if G > 1:
        mesh = jax.sharding.get_abstract_mesh()
        dp = tuple(a for a in ("pod", "data") if mesh is not None and a in mesh.shape)
        import numpy as _np
        dp_n = int(_np.prod([mesh.shape[a] for a in dp])) if dp else 1
        if dp and G == dp_n:
            from jax.sharding import PartitionSpec as _P

            def local(xl):  # xl: [B/dp, S, d] — one dispatch group
                out, aux = _moe_dense(cfg, p, xl, 1)
                # NOTE: aux is the LOCAL group's load-balance estimate; the
                # cross-group mean is taken outside (an inner pmean trips an
                # XLA-CPU AllReducePromotion bug — see EXPERIMENTS.md §Perf).
                return out, aux[None]

            fn = jax.shard_map(
                local,
                mesh=mesh,
                in_specs=(_P(dp, None, None),),
                out_specs=(_P(dp, None, None), _P(dp)),
                axis_names=set(dp),
                check_vma=False,
            )
            out, aux = fn(x)
            return out, jnp.mean(aux)
    return _moe_dense(cfg, p, x, G)


def _moe_dense(cfg: ArchConfig, p: Dict, x: jax.Array, G: int) -> Tuple[jax.Array, jax.Array]:
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    N = B * S
    n = N // G  # tokens per dispatch group
    xf = x.reshape(G, n, d)

    logits = jnp.einsum("gnd,de->gne", xf.astype(jnp.float32), p["router"])  # [G, n, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)  # [G, n, k]
    if k > 1:  # renormalise the selected gates
        gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # Switch-style aux loss: E * sum_e f_e * P_e (global means)
    me = jnp.mean(probs, axis=(0, 1))  # [E]
    ce = jnp.sum(
        jax.nn.one_hot(expert_ids[..., 0], E, dtype=jnp.float32), axis=(0, 1)
    ) / N  # [E]
    aux = E * jnp.sum(me * ce)

    # ---- sort-based dispatch (per group) ---------------------------------
    # ceil, with a small floor so tiny decode batches (N ~ B) don't drop
    # tokens on router collisions
    cap = int(max(-(-n * k // E) * cfg.capacity_factor, min(n * k, 8)))
    nk = n * k
    flat_expert = expert_ids.reshape(G, nk)
    flat_gate = gate_vals.reshape(G, nk)
    flat_token = jnp.broadcast_to(
        jnp.repeat(jnp.arange(n), k)[None], (G, nk)
    )

    order = jnp.argsort(flat_expert, axis=-1)  # stable, per group
    se = jnp.take_along_axis(flat_expert, order, axis=-1)
    st = jnp.take_along_axis(flat_token, order, axis=-1)
    sg = jnp.take_along_axis(flat_gate, order, axis=-1)
    # rank within expert = running index - index of expert's first slot
    idx = jnp.arange(nk)[None]
    first = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(E), side="left"))(se)
    rank = idx - jnp.take_along_axis(first, se, axis=-1)
    keep = rank < cap
    slot = se * cap + rank  # in [0, E*cap)

    # gather tokens into expert buffers [G, E*cap, d]
    def build_buf(slot_g, keep_g, st_g, sg_g):
        buf_tok = jnp.full((E * cap,), n, jnp.int32)  # n = dummy row
        buf_tok = buf_tok.at[jnp.where(keep_g, slot_g, E * cap)].set(
            st_g.astype(jnp.int32), mode="drop"
        )
        gates = jnp.zeros((E * cap,), jnp.float32).at[
            jnp.where(keep_g, slot_g, E * cap)
        ].set(sg_g, mode="drop")
        return buf_tok, gates

    buf_tok, gates_slot = jax.vmap(build_buf)(slot, keep, st, sg)  # [G, E*cap]
    xpad = jnp.concatenate([xf, jnp.zeros((G, 1, d), xf.dtype)], axis=1)
    inp = jnp.take_along_axis(
        xpad, buf_tok[:, :, None].astype(jnp.int32), axis=1
    ).reshape(G, E, cap, d)

    exp_axes = ("experts", "embed", "ff")
    h = jax.nn.silu(
        jnp.einsum("gecd,edf->gecf", inp, _mg(p["w_gate"], exp_axes))
    ) * jnp.einsum("gecd,edf->gecf", inp, _mg(p["w_up"], exp_axes))
    out_e = jnp.einsum(
        "gecf,efd->gecd", h, _mg(p["w_down"], ("experts", "ff", "embed"))
    ).reshape(G, E * cap, d)

    # combine back: scatter-add gate-weighted expert outputs to tokens
    valid = (buf_tok < n).astype(out_e.dtype)
    contrib = out_e * (gates_slot * valid)[:, :, None].astype(out_e.dtype)

    def combine(buf_tok_g, contrib_g):
        return jnp.zeros((n + 1, d), contrib_g.dtype).at[buf_tok_g].add(
            contrib_g, mode="drop"
        )[:n]

    out = jax.vmap(combine)(buf_tok, contrib)
    return out.reshape(B, S, d).astype(x.dtype), aux
