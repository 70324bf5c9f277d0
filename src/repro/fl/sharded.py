"""SPMD MAFL: the AdaBoost.F round as a shard_map program over the
production mesh (DESIGN.md §2 table) — the TPU-native re-expression of
the paper's gRPC protocol:

  collaborator i        = index group along the (pod, data) mesh axes
  hypothesis broadcast  = lax.all_gather of the weak-hypothesis pytree
  error report          = lax.psum of per-collaborator error vectors
  synch barrier         = SPMD lockstep (structural)

The model axis replicates the (small) tabular weak learners; it exists so
the FL round composes with model-parallel DNN workloads on one mesh.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import scoring
from repro.core.boosting import BoostState, Ensemble, _samme_alpha, _set_slot, _take_slot
from repro.kernels import ops
from repro.learners.base import LearnerSpec, WeakLearner


def federation_mesh(n_collaborators: int) -> Mesh:
    """The SPMD round's ``(data, model)`` mesh over every visible device:
    one ``data`` index per collaborator, the remaining devices on
    ``model``.  Its axes are Auto: the round and the host code around it
    (``f1_macro`` on the batch-sharded predictions) rely on sharding
    propagation, which Explicit axes — ``jax.make_mesh``'s default —
    turn into errors."""
    n_dev = len(jax.devices())
    if n_dev % n_collaborators:
        raise ValueError(
            f"{n_collaborators} collaborators do not divide over {n_dev} devices; "
            "on CPU run under XLA_FLAGS=--xla_force_host_platform_device_count=<C*m>"
        )
    return jax.make_mesh(
        (n_collaborators, n_dev // n_collaborators), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


def fl_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _pack_leaves(tree):
    """Flatten a (f32/i32) pytree into ONE i32 wire buffer + metadata —
    the paper's gRPC buffer-packing optimisation applied to the
    hypothesis-broadcast collective (§Perf iteration: one all-gather
    instead of one per leaf).

    Float leaves travel bitcast into the integer buffer.  The buffer is
    integer because XLA:TPU lowers an all-gather to an all-reduce (a sum
    into zeros), which is exact on integers but flushes f32 denormals —
    and a small i32 such as a tree's feature index, bitcast to f32, is a
    denormal."""
    leaves, treedef = jax.tree.flatten(tree)
    flats, meta, off = [], [], 0
    for l in leaves:
        fl = l.reshape(-1)
        kind = str(l.dtype)
        if fl.dtype != jnp.int32:
            fl = jax.lax.bitcast_convert_type(fl.astype(jnp.float32), jnp.int32)
        flats.append(fl)
        meta.append((off, l.shape, kind))
        off += fl.shape[0]
    return jnp.concatenate(flats), (treedef, meta)


def _unpack_leaves(buf, fmt, lead=()):
    """Inverse of _pack_leaves; ``lead`` = extra gathered leading dims."""
    treedef, meta = fmt
    leaves = []
    for off, shape, kind in meta:
        n = 1
        for s in shape:
            n *= s
        fl = jax.lax.dynamic_slice_in_dim(buf, off, n, axis=-1)
        if kind != "int32":
            fl = jax.lax.bitcast_convert_type(fl, jnp.float32).astype(kind)
        leaves.append(fl.reshape(lead + shape))
    return jax.tree.unflatten(treedef, leaves)


def sharded_adaboost_round(
    learner: WeakLearner,
    spec: LearnerSpec,
    mesh: Mesh,
    state: BoostState,
    X: jax.Array,  # [C, n, d]  — C == prod(pod, data) collaborators
    y: jax.Array,  # [C, n]
    mask: jax.Array,  # [C, n]
    *,
    packed_broadcast: bool = True,
    use_pallas: bool = False,
):
    """One AdaBoost.F round, collaborator-parallel over the mesh.

    ``packed_broadcast`` (default ON — the §5.1 buffer-packing analogue)
    flattens the weak-hypothesis pytree into one i32 wire buffer so the
    broadcast is ONE all-gather per round instead of one per leaf; flip
    off for the pre-optimisation per-leaf behaviour (the
    ``+packed_broadcast`` ablation stage in bench_optimizations).

    Step 2 reuses the shard-static fit cache (``state.fit_cache``, e.g.
    the trees' ``BinnedDataset``): digitization/quantile work happens
    once per shard at state init, never inside the round program.

    Step 3 is predict-once per shard: the [C, n] prediction matrix is
    materialised a single time, the local error vector is a kernel-backed
    ``weighted_errors`` reduction over it (then ``psum`` across the
    federation axes), and the chosen hypothesis's mispredictions are a
    row slice of the same matrix — never a second predict.
    """
    axes = fl_axes(mesh)
    has_cache = state.fit_cache is not None and learner.fit_cached is not None

    def body(ens_params, ens_alpha, ens_count, w, key, Xl, yl, ml, *cache_l):
        # local block: [1, n, d] — this device group IS collaborator i
        Xi, yi, wi, mi = Xl[0], yl[0], w[0], ml[0]
        idx = jnp.zeros((), jnp.int32)
        for a in axes:  # flat collaborator index across (pod, data)
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        kfit = jax.random.fold_in(key, idx)

        # paper step 2: local training + hypothesis-space broadcast
        w_fit = wi / jnp.maximum(jnp.sum(wi), 1e-30) * jnp.maximum(jnp.sum(mi), 1.0)
        if has_cache:  # shard-static precomputation (binning etc.)
            cache_i = jax.tree.map(lambda x: x[0], cache_l[0])
            h_local = learner.fit_cached(spec, None, Xi, yi, w_fit, kfit, cache_i)
        else:
            h_local = learner.fit(spec, None, Xi, yi, w_fit, kfit)
        if packed_broadcast:  # one collective for the whole hypothesis
            buf, fmt = _pack_leaves(h_local)
            gathered = _multi_gather(buf, axes)  # [C, total]
            hyps = _unpack_leaves(gathered, fmt, lead=(gathered.shape[0],))
        else:  # per-leaf all-gathers (pre-optimisation OpenFL behaviour)
            hyps = jax.tree.map(lambda l: _multi_gather(l, axes), h_local)
        # hyps: [C, ...] — every collaborator now holds the full space

        # paper step 3: score the whole space on the local shard — predict
        # ONCE, then reduce with the kernel-backed weighted-error sum
        preds = scoring.predict_matrix(learner, spec, hyps, Xi)  # [C, n]
        local_errs = scoring.shard_errors(preds, yi, wi * mi, use_pallas=use_pallas)
        eps = _multi_psum(local_errs, axes)  # weights globally normalised

        # paper step 4 (aggregator, replicated): select + alpha + append
        c = jnp.argmin(eps)
        alpha = _samme_alpha(eps[c], spec.n_classes)
        chosen = _take_slot(hyps, c)
        ens_params = _set_slot(ens_params, ens_count, chosen)
        ens_alpha = ens_alpha.at[ens_count].set(alpha)
        ens_count = ens_count + 1

        # weight update + global renormalisation (the 'norm exchange');
        # the chosen hypothesis's mispredictions are a row slice of preds
        mis = scoring.chosen_mis(preds, yi, c)
        wi = scoring.update_weights(
            wi, mis, mi, alpha, use_pallas=use_pallas,
            renormalize=False,  # renorm needs the cross-shard psum'd total below
        )
        total = _multi_psum(jnp.sum(wi), axes)
        wi = wi / jnp.maximum(total, 1e-30)
        metrics = {"epsilon": eps[c], "alpha": alpha, "chosen": c.astype(jnp.int32)}
        return ens_params, ens_alpha, ens_count, wi[None], metrics

    coll = P(axes) if axes else P()
    cache_args = (state.fit_cache,) if has_cache else ()
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(), P(), coll, P(), coll, coll, coll) + (coll,) * len(cache_args),
        out_specs=(P(), P(), P(), coll, P()),
        check_vma=False,
    )
    ens = state.ensemble
    ens_params, ens_alpha, ens_count, w, metrics = fn(
        ens.params, ens.alpha, ens.count, state.weights, state.key, X, y, mask,
        *cache_args,
    )
    key = jax.random.fold_in(state.key, 1)
    return (
        BoostState(Ensemble(ens_params, ens_alpha, ens_count), w, key, state.fit_cache),
        metrics,
    )


def _multi_gather(x, axes):
    for a in reversed(axes):
        x = jax.lax.all_gather(x, a)
    return x.reshape((-1,) + x.shape[len(axes) :])


def _multi_psum(x, axes):
    for a in axes:
        x = jax.lax.psum(x, a)
    return x


def make_batch_predict(
    learner: WeakLearner,
    spec: LearnerSpec,
    mesh: Mesh,
    *,
    committee: bool = False,
    use_pallas: bool = False,
):
    """Batch-sharded jitted ensemble predict — the serving engine's
    mesh backend (``serve/engine.EngineConfig(mesh=...)``).

    Returns ``fn(params, alpha, count, X) -> [n] i32`` where the batch
    axis of ``X`` is split over the mesh's federation axes (params and
    alpha replicate): every shard scores its slice of the batch with the
    SAME member-vote + ``vote_argmax`` program the local engine runs, so
    sharded answers are bit-for-bit the local answers.  ``n`` must
    divide by the federation shard count — the engine guarantees this by
    admission (static batches padded to a ``batch_size`` validated
    against the mesh)."""
    axes = fl_axes(mesh)

    def body(params, alpha, count, Xl):
        T = alpha.shape[0]
        member = lambda t: scoring.member_prediction(
            learner, spec, _take_slot(params, t), Xl, committee=committee
        )
        preds = jax.vmap(member)(jnp.arange(T))  # [T, n/shards]
        used = (jnp.arange(T) < count).astype(jnp.float32) * alpha
        return ops.vote_argmax(
            preds, used, n_classes=spec.n_classes, use_pallas=use_pallas
        )

    coll = P(axes) if axes else P()
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(), P(), coll), out_specs=coll, check_vma=False
    )
    return jax.jit(fn)


def sharded_strong_predict(
    learner: WeakLearner, spec: LearnerSpec, mesh: Mesh, ens: Ensemble, X: jax.Array,
    *, committee: bool = False, use_pallas: bool = False,
) -> jax.Array:
    """Ensemble inference, batch-sharded over the federation axes (the
    one-shot convenience over :func:`make_batch_predict`)."""
    fn = make_batch_predict(
        learner, spec, mesh, committee=committee, use_pallas=use_pallas
    )
    return fn(ens.params, ens.alpha, ens.count, X)
