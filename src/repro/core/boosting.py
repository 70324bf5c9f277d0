"""Model-agnostic federated boosting — AdaBoost.F, DistBoost.F, PreWeak.F
and Federated Bagging (paper §3, Fig. 1), plus the centralized AdaBoost
(SAMME) oracle used as the Table-1 "Reference".

Data layout: collaborator-stacked fixed shapes —
    X [C, n, d]   y [C, n]   mask [C, n]  (padding -> mask 0)
Sample weights live in the state as w [C, n], globally normalised
(sum over ALL collaborators == 1), exactly the quantity the paper's
step-1 "dataset size N" exchange exists to maintain.

Everything here is pure and jit-able; ``fl/sharded.py`` re-expresses the
same round as an SPMD program over the mesh's data axis, where the
``all_hypotheses`` stacking below becomes ``lax.all_gather`` and the
error-matrix reduction becomes ``lax.psum``.

The step-3/4 hot path (whole-space scoring + weight update) runs through
the predict-once engine in ``core/scoring.py``: each round materialises
the prediction tensor exactly once and every error/misprediction/weight
quantity is a (optionally Pallas-kernel-backed) reduction over it.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import scoring
from repro.learners.base import LearnerSpec, WeakLearner

# ---------------------------------------------------------------------------
# Ensemble (the "strong hypothesis")
# ---------------------------------------------------------------------------


class Ensemble(NamedTuple):
    """Pre-allocated strong hypothesis: T slots of weak-hypothesis pytrees."""

    params: Any  # pytree, every leaf has leading dim T (or [T, C] for committees)
    alpha: jax.Array  # [T]
    count: jax.Array  # scalar i32 — slots used so far


def _stack_slots(template: Any, T: int) -> Any:
    return jax.tree.map(lambda x: jnp.zeros((T,) + x.shape, x.dtype), template)


_take_slot = scoring._take_slot  # single canonical slot-select helper


def _set_slot(buf: Any, t, value: Any) -> Any:
    return jax.tree.map(lambda b, v: b.at[t].set(v), buf, value)


def init_ensemble(learner: WeakLearner, spec: LearnerSpec, T: int, key: jax.Array,
                  committee_size: int | None = None) -> Ensemble:
    proto = learner.init(spec, key)
    if committee_size is not None:  # DistBoost.F stores a committee per round
        proto = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (committee_size,) + x.shape), proto
        )
    return Ensemble(
        params=_stack_slots(proto, T),
        alpha=jnp.zeros((T,), jnp.float32),
        count=jnp.zeros((), jnp.int32),
    )


def ensemble_votes(
    learner: WeakLearner, spec: LearnerSpec, ens: Ensemble, X: jax.Array,
    *, committee: bool = False,
) -> jax.Array:
    """alpha-weighted vote tally [n, K] over the used slots."""
    T = ens.alpha.shape[0]

    def member_pred(params_t):
        return scoring.member_prediction(learner, spec, params_t, X, committee=committee)

    preds = jax.vmap(lambda t: member_pred(_take_slot(ens.params, t)))(jnp.arange(T))  # [T, n]
    used = (jnp.arange(T) < ens.count).astype(jnp.float32) * ens.alpha  # [T]
    onehot = jax.nn.one_hot(preds, spec.n_classes)  # [T, n, K]
    return jnp.einsum("t,tnk->nk", used, onehot)


def strong_predict(learner, spec, ens: Ensemble, X, *, committee: bool = False) -> jax.Array:
    return jnp.argmax(ensemble_votes(learner, spec, ens, X, committee=committee), axis=-1)


# ---------------------------------------------------------------------------
# Shared round machinery
# ---------------------------------------------------------------------------


class BoostState(NamedTuple):
    ensemble: Ensemble
    weights: jax.Array  # [C, n] — globally normalised sample weights
    key: jax.Array
    # Per-collaborator X-only fit precomputation — an arbitrary cache
    # pytree per the ``WeakLearner.precompute`` contract (the trees carry
    # a ``learners/binning.py::BinnedDataset``: quantile edges + digitized
    # bin indices).  X is static per collaborator across rounds, so this
    # is computed once at init and threaded through every round; fitting
    # never re-touches the raw shard.
    fit_cache: Any = None


def init_boost_state(
    learner: WeakLearner,
    spec: LearnerSpec,
    T: int,
    mask: jax.Array,  # [C, n]
    key: jax.Array,
    *,
    committee_size: int | None = None,
    X: jax.Array | None = None,  # [C, n, d] — enables the fit cache
) -> BoostState:
    k1, k2 = jax.random.split(key)
    w = mask / jnp.maximum(jnp.sum(mask), 1.0)  # uniform over the GLOBAL dataset
    cache = None
    if X is not None and learner.precompute is not None and learner.fit_cached is not None:
        cache = jax.vmap(lambda Xi: learner.precompute(spec, Xi))(X)  # [C, ...]
    return BoostState(
        ensemble=init_ensemble(learner, spec, T, k1, committee_size=committee_size),
        weights=w.astype(jnp.float32),
        key=k2,
        fit_cache=cache,
    )


def _local_fits(
    learner, spec, w, X, y, key, fit_cache=None,
    *, batched=True, use_pallas=False, block_s=None, block_d=None,
    keys=None,
):
    """Train one weak hypothesis per collaborator (paper step 2). [C, ...]

    Three routes, fastest available first:
      * ``fit_batched`` — ONE tensor program fits all C hypotheses
        (kernel-backed learners issue one launch per stage instead of C);
        requires the shard-static fit cache and ``batched=True``;
      * ``vmap(fit_cached)`` — per-collaborator fits reusing the cache;
      * ``vmap(fit)``       — no cache (X-derived scaffold recomputed).
    All three agree bit-for-bit on the oracle path (``use_pallas=False``)
    — regression-tested in tests/test_binning.py.

    ``keys`` overrides the per-collaborator key split: a heterogeneous
    round splits ONE round key across all C collaborators and hands each
    learner group its members' slice, so grouping never changes which
    key a collaborator fits with (``core/hetero.py``).
    """
    C = X.shape[0]
    if keys is None:
        keys = jax.random.split(key, C)

    if batched and fit_cache is not None and learner.fit_batched is not None:
        return learner.fit_batched(
            spec, X, y, w, keys, fit_cache,
            use_pallas=use_pallas, block_s=block_s, block_d=block_d,
        )

    dummy = learner.init(spec, keys[0])

    if fit_cache is not None and learner.fit_cached is not None:
        def fit_one_cached(Xi, yi, wi, ki, ci):
            return learner.fit_cached(spec, dummy, Xi, yi, wi, ki, ci)

        return jax.vmap(fit_one_cached)(X, y, w, keys, fit_cache)

    def fit_one(Xi, yi, wi, ki):
        return learner.fit(spec, dummy, Xi, yi, wi, ki)

    return jax.vmap(fit_one)(X, y, w, keys)


def _samme_alpha(eps: jax.Array, n_classes: int) -> jax.Array:
    eps = jnp.clip(eps, 1e-10, 1.0 - 1e-10)
    return jnp.clip(jnp.log((1.0 - eps) / eps) + jnp.log(n_classes - 1.0), -10.0, 10.0)


def run_stages(stages, state: BoostState, X, y, mask):
    """Compose a round's named stages into the full round step.

    Every round below is built from (name, fn) stages with the uniform
    signature ``fn(state, carry, X, y, mask) -> (state, carry)`` — the
    final stage leaves the round metrics in ``carry["metrics"]``.  The
    fused round functions jit THIS composition.  Each stage runs under
    ``jax.named_scope(<name>)``, so its ops carry ``fit`` / ``score`` /
    ``aggregate`` in their metadata and a profiler trace of the one
    fused program attributes device time to the round's steps.

    An ``optimization_barrier`` seals each stage boundary so XLA cannot
    fuse reductions ACROSS stages (e.g. folding the score stage's error
    matrix straight into the aggregate stage's eps sum, which reassociates
    the reduction).  This pins one canonical numeric result for a round:
    the one fused jit, each stage jitted alone, and the per-collaborator
    distributed runtime (``fl/distributed.py`` — where the stage boundary
    is a real network collective and fusing across it is impossible) are
    all bit-for-bit identical, which is what the multi-process equivalence
    tests assert.  The barrier only limits inter-stage fusion; each
    stage's internals compile exactly as before.
    """
    carry: Dict[str, Any] = {}
    for name, fn in stages:
        with jax.named_scope(name):
            state, carry = fn(state, carry, X, y, mask)
        state, carry = jax.lax.optimization_barrier((state, carry))
    return state, carry["metrics"]


# ---------------------------------------------------------------------------
# AdaBoost.F (paper's implemented algorithm)
# ---------------------------------------------------------------------------


def adaboost_f_stages(
    learner: WeakLearner,
    spec: LearnerSpec,
    *,
    use_pallas: bool = False,
    batched_fit: bool = True,
    block_s: int | None = None,
    block_d: int | None = None,
):
    """The AdaBoost.F round as named stages (see :func:`run_stages`)."""

    def fit(state, carry, X, y, mask):
        key, kfit = jax.random.split(state.key)
        # step 2: local training, all C fits as one batched tensor program
        # when the learner supports it (BinnedDataset caches etc. come
        # from the round-static fit cache)
        hyps = _local_fits(
            learner, spec, state.weights, X, y, kfit, state.fit_cache,
            batched=batched_fit, use_pallas=use_pallas,
            block_s=block_s, block_d=block_d,
        )  # [C, ...]
        return BoostState(state.ensemble, state.weights, key, state.fit_cache), {
            "hyps": hyps
        }

    def score(state, carry, X, y, mask):
        # step 3: predict ONCE per (hypothesis, shard) — every quantity
        # downstream is a reduction over this tensor, never a second predict
        preds = scoring.predict_tensor(learner, spec, carry["hyps"], X)  # [C, C, n]
        errs = scoring.error_matrix(preds, y, state.weights, use_pallas=use_pallas)
        return state, {**carry, "preds": preds, "errs": errs}

    def aggregate(state, carry, X, y, mask):
        # step 4 (aggregator): globally-weighted error, best hypothesis, alpha
        hyps, preds, errs = carry["hyps"], carry["preds"], carry["errs"]
        eps = jnp.sum(errs, axis=0)  # w globally normalised: sum_i ||w_i|| == 1
        c = jnp.argmin(eps)
        alpha = _samme_alpha(eps[c], spec.n_classes)
        chosen = _take_slot(hyps, c)

        ens = state.ensemble
        ens = Ensemble(
            params=_set_slot(ens.params, ens.count, chosen),
            alpha=ens.alpha.at[ens.count].set(alpha),
            count=ens.count + 1,
        )
        mis = scoring.chosen_mis(preds, y, c)  # row slice of preds
        w = scoring.update_weights(state.weights, mis, mask, alpha, use_pallas=use_pallas)
        metrics = {"epsilon": eps[c], "alpha": alpha, "chosen": c.astype(jnp.int32)}
        return BoostState(ens, w, state.key, state.fit_cache), {"metrics": metrics}

    return [("fit", fit), ("score", score), ("aggregate", aggregate)]


def adaboost_f_round(
    learner: WeakLearner,
    spec: LearnerSpec,
    state: BoostState,
    X: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    *,
    use_pallas: bool = False,
    batched_fit: bool = True,
    block_s: int | None = None,
    block_d: int | None = None,
) -> Tuple[BoostState, Dict[str, jax.Array]]:
    return run_stages(
        adaboost_f_stages(
            learner, spec, use_pallas=use_pallas, batched_fit=batched_fit,
            block_s=block_s, block_d=block_d,
        ),
        state, X, y, mask,
    )


# ---------------------------------------------------------------------------
# DistBoost.F — the round hypothesis is the committee of all local models
# ---------------------------------------------------------------------------


def _committee_predict(learner, spec, committee, X):
    preds = jax.vmap(lambda p: learner.predict(spec, p, X))(committee)  # [C, n]
    tally = jnp.sum(jax.nn.one_hot(preds, spec.n_classes), axis=0)
    return jnp.argmax(tally, axis=-1).astype(jnp.int32)


def distboost_f_stages(
    learner, spec, *,
    use_pallas: bool = False, batched_fit: bool = True,
    block_s: int | None = None, block_d: int | None = None,
):
    """The DistBoost.F round as named stages (see :func:`run_stages`)."""

    def fit(state, carry, X, y, mask):
        key, kfit = jax.random.split(state.key)
        committee = _local_fits(
            learner, spec, state.weights, X, y, kfit, state.fit_cache,
            batched=batched_fit, use_pallas=use_pallas,
            block_s=block_s, block_d=block_d,
        )  # [C, ...]
        return BoostState(state.ensemble, state.weights, key, state.fit_cache), {
            "committee": committee
        }

    def score(state, carry, X, y, mask):
        committee = carry["committee"]

        def mis_one(Xi, yi):
            return (
                _committee_predict(learner, spec, committee, Xi) != yi
            ).astype(jnp.float32)

        mis = jax.vmap(mis_one)(X, y)  # [C, n] — the round's ONLY predict pass
        return state, {**carry, "mis": mis}

    def aggregate(state, carry, X, y, mask):
        committee, mis = carry["committee"], carry["mis"]
        w = state.weights
        eps = jnp.sum(w * mis)
        alpha = _samme_alpha(eps, spec.n_classes)

        ens = state.ensemble
        ens = Ensemble(
            params=_set_slot(ens.params, ens.count, committee),
            alpha=ens.alpha.at[ens.count].set(alpha),
            count=ens.count + 1,
        )
        w = scoring.update_weights(w, mis, mask, alpha, use_pallas=use_pallas)
        metrics = {"epsilon": eps, "alpha": alpha, "chosen": jnp.zeros((), jnp.int32)}
        return BoostState(ens, w, state.key, state.fit_cache), {"metrics": metrics}

    return [("fit", fit), ("score", score), ("aggregate", aggregate)]


def distboost_f_round(
    learner, spec, state, X, y, mask, *,
    use_pallas: bool = False, batched_fit: bool = True,
    block_s: int | None = None, block_d: int | None = None,
):
    return run_stages(
        distboost_f_stages(
            learner, spec, use_pallas=use_pallas, batched_fit=batched_fit,
            block_s=block_s, block_d=block_d,
        ),
        state, X, y, mask,
    )


# ---------------------------------------------------------------------------
# PreWeak.F — search a pre-trained C x T hypothesis space
# ---------------------------------------------------------------------------


def _preweak_local_space(learner, spec, X, y, mask, keys, fit_cache, T: int):
    """Steps 1+2 of PreWeak.F for one learner group: every collaborator
    in the ``[C, ...]`` stack runs T rounds of LOCAL AdaBoost with its
    per-collaborator key; returns the flat ``[C*T, ...]`` hypothesis
    block.  Shared by the homogeneous setup below and the grouped
    heterogeneous setup in ``core/hetero.py``."""
    C = y.shape[0]
    cached = learner.precompute is not None and learner.fit_cached is not None

    def local_adaboost(Xi, yi, mi, ki, cache_i):
        wi = mi / jnp.maximum(jnp.sum(mi), 1.0)
        dummy = learner.init(spec, ki)
        # X is static across the T local rounds: the fit cache
        # (BinnedDataset for trees) comes from the round state when the
        # caller built one, else is computed once here instead of once
        # per local round.
        cache = cache_i
        if cache is None and cached:
            cache = learner.precompute(spec, Xi)

        def round_(carry, kt):
            w, _ = carry, None
            p = (
                learner.fit_cached(spec, dummy, Xi, yi, w, kt, cache)
                if cached
                else learner.fit(spec, dummy, Xi, yi, w, kt)
            )
            mis = (learner.predict(spec, p, Xi) != yi).astype(jnp.float32)
            e = jnp.sum(w * mis) / jnp.maximum(jnp.sum(w), 1e-30)
            a = _samme_alpha(e, spec.n_classes)
            w = w * jnp.exp(a * mis) * mi
            w = w / jnp.maximum(jnp.sum(w), 1e-30)
            return w, p

        _, ps = jax.lax.scan(round_, wi, jax.random.split(ki, T))
        return ps  # [T, ...]

    if fit_cache is not None and cached:
        hyps = jax.vmap(local_adaboost)(X, y, mask, keys, fit_cache)
    else:
        hyps = jax.vmap(
            lambda Xi, yi, mi, ki: local_adaboost(Xi, yi, mi, ki, None)
        )(X, y, mask, keys)  # [C, T, ...]
    return jax.tree.map(lambda x: x.reshape((C * T,) + x.shape[2:]), hyps)


def preweak_f_setup(learner, spec, state, X, y, mask, T: int):
    """Fuse steps 1+2: every collaborator runs T rounds of LOCAL AdaBoost,
    shipping all T hypotheses; the federation then owns a C*T space."""
    C, n = y.shape
    keys = jax.random.split(state.key, C + 1)
    flat = _preweak_local_space(learner, spec, X, y, mask, keys[:C], state.fit_cache, T)
    return flat, BoostState(state.ensemble, state.weights, keys[-1], state.fit_cache)


def preweak_f_predictions(learner, spec, hyp_space, X) -> jax.Array:
    """Setup-time prediction cache [C, C*T, n] for the static hypothesis
    space: PreWeak.F's C*T hypotheses never change across rounds, so the
    whole-space scoring of every round can reuse this one tensor —
    O(H*n) reduction per round instead of O(H*n*predict)."""
    return scoring.predict_tensor(learner, spec, hyp_space, X)


def preweak_f_stages(learner, spec, hyp_space, *,
                     pred_cache: jax.Array | None = None,
                     use_pallas: bool = False):
    """The PreWeak.F round as named stages (see :func:`run_stages`).

    No fit stage — the hypothesis space is pre-trained at setup."""

    def score(state, carry, X, y, mask):
        preds = pred_cache if pred_cache is not None else preweak_f_predictions(
            learner, spec, hyp_space, X
        )  # [C, C*T, n]
        errs = scoring.error_matrix(preds, y, state.weights, use_pallas=use_pallas)
        return state, {"preds": preds, "errs": errs}

    def aggregate(state, carry, X, y, mask):
        preds, errs = carry["preds"], carry["errs"]
        eps = jnp.sum(errs, axis=0)
        c = jnp.argmin(eps)
        alpha = _samme_alpha(eps[c], spec.n_classes)
        chosen = _take_slot(hyp_space, c)

        ens = state.ensemble
        ens = Ensemble(
            params=_set_slot(ens.params, ens.count, chosen),
            alpha=ens.alpha.at[ens.count].set(alpha),
            count=ens.count + 1,
        )
        mis = scoring.chosen_mis(preds, y, c)  # row slice of preds
        w = scoring.update_weights(state.weights, mis, mask, alpha, use_pallas=use_pallas)
        metrics = {"epsilon": eps[c], "alpha": alpha, "chosen": c.astype(jnp.int32)}
        return BoostState(ens, w, state.key, state.fit_cache), {"metrics": metrics}

    return [("score", score), ("aggregate", aggregate)]


def preweak_f_round(learner, spec, state, hyp_space, X, y, mask, *,
                    pred_cache: jax.Array | None = None, use_pallas: bool = False):
    """Rounds loop only on steps 3-4 (red dotted line in Fig. 1).

    With ``pred_cache`` (from :func:`preweak_f_predictions`) the round is
    a pure weighted reduction over the cached predictions; without it the
    space is re-predicted each round (the pre-optimisation behaviour).
    """
    return run_stages(
        preweak_f_stages(
            learner, spec, hyp_space, pred_cache=pred_cache, use_pallas=use_pallas
        ),
        state, X, y, mask,
    )


# ---------------------------------------------------------------------------
# Federated Bagging — omit adaboost_update (paper §4.1)
# ---------------------------------------------------------------------------


def bagging_stages(
    learner, spec, *,
    use_pallas: bool = False, batched_fit: bool = True,
    block_s: int | None = None, block_d: int | None = None,
):
    """The federated-bagging round as named stages (see :func:`run_stages`).

    No score stage — bagging skips the whole scoring reduction; the
    kernel flags only steer the fit."""

    def fit(state, carry, X, y, mask):
        key, kfit, kpick = jax.random.split(state.key, 3)
        w = mask / jnp.maximum(jnp.sum(mask, axis=1, keepdims=True), 1.0)  # local-uniform
        hyps = _local_fits(
            learner, spec, w, X, y, kfit, state.fit_cache,
            batched=batched_fit, use_pallas=use_pallas,
            block_s=block_s, block_d=block_d,
        )
        return BoostState(state.ensemble, state.weights, key, state.fit_cache), {
            "hyps": hyps, "kpick": kpick
        }

    def aggregate(state, carry, X, y, mask):
        hyps, kpick = carry["hyps"], carry["kpick"]
        c = jax.random.randint(kpick, (), 0, X.shape[0])  # rotate members round-robin-ish
        ens = state.ensemble
        ens = Ensemble(
            params=_set_slot(ens.params, ens.count, _take_slot(hyps, c)),
            alpha=ens.alpha.at[ens.count].set(1.0),  # unweighted vote
            count=ens.count + 1,
        )
        metrics = {
            "epsilon": jnp.zeros(()), "alpha": jnp.ones(()),
            "chosen": c.astype(jnp.int32),
        }
        return BoostState(ens, state.weights, state.key, state.fit_cache), {
            "metrics": metrics
        }

    return [("fit", fit), ("aggregate", aggregate)]


def bagging_round(
    learner, spec, state, X, y, mask, *,
    use_pallas: bool = False, batched_fit: bool = True,
    block_s: int | None = None, block_d: int | None = None,
):
    return run_stages(
        bagging_stages(
            learner, spec, use_pallas=use_pallas, batched_fit=batched_fit,
            block_s=block_s, block_d=block_d,
        ),
        state, X, y, mask,
    )


# ---------------------------------------------------------------------------
# Centralized AdaBoost (SAMME) — Table 1 "Reference" oracle
# ---------------------------------------------------------------------------


def centralized_adaboost(
    learner: WeakLearner,
    spec: LearnerSpec,
    X: jax.Array,  # [n, d] pooled
    y: jax.Array,
    T: int,
    key: jax.Array,
) -> Ensemble:
    mask = jnp.ones(y.shape, jnp.float32)
    Xc, yc, mc = X[None], y[None], mask[None]
    state = init_boost_state(learner, spec, T, mc, key, X=Xc)

    def round_(state, _):
        state, m = adaboost_f_round(learner, spec, state, Xc, yc, mc)
        return state, m

    state, _ = jax.lax.scan(round_, state, None, length=T)
    return state.ensemble


ROUND_FNS: Dict[str, Callable] = {
    "adaboost_f": adaboost_f_round,
    "distboost_f": distboost_f_round,
    "bagging": bagging_round,
}
