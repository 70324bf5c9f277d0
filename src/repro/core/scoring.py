"""Predict-once scoring engine — the canonical round structure for
MAFL's step-3 hot-spot.

Every federated boosting round makes each collaborator score the WHOLE
hypothesis space on its local shard (paper step 3): H x n work per
collaborator, the reduction the §5.1 framework optimisations exist to
feed.  The naive expression of a round invokes ``learner.predict``
multiple times on the same (hypothesis, shard) pair — once for the
error matrix, once more for the chosen hypothesis's mispredictions at
weight-update time.  Following the paper's own profiling lesson
(framework plumbing around the learner, not the learner, dominates
round time), this module makes **predict once, reduce many** canonical:

  * ``predict_matrix`` / ``predict_tensor`` — materialise the
    prediction matrix ``preds [H, n]`` (or ``[C, H, n]``) exactly once;
  * ``error_matrix``  — kernel-backed ``eps[i, h]`` reduction over the
    materialised predictions (``kernels.ops.weighted_errors``);
  * ``chosen_mis``    — the chosen hypothesis's misprediction vector is
    a ROW SLICE of ``preds``, never a second predict;
  * ``update_weights`` — fused ``w * exp(alpha*mis) * mask`` + global
    renormalisation (``kernels.ops.weight_update``);
  * ``VoteTally`` — incremental ensemble evaluation: a running ``[n, K]``
    vote tally that adds only the NEWLY appended members' votes each
    eval instead of re-predicting all T ensemble slots.

Prediction caching for static hypothesis spaces (PreWeak.F's C*T space
never changes across rounds) is just ``predict_tensor`` called once at
setup and the resulting tensor fed back into every round — see
``boosting.preweak_f_round(pred_cache=...)``.

Everything is pure and jit-able.  ``use_pallas`` dispatches the Pallas
TPU kernels (interpret mode off-TPU) vs the pure-jnp oracles in
``kernels/ref.py``; both paths agree to float32 tolerance and are swept
against each other in tests/test_scoring.py.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.learners.base import LearnerSpec, WeakLearner


def _take_slot(params: Any, t) -> Any:
    return jax.tree.map(lambda x: x[t], params)


# ---------------------------------------------------------------------------
# Predict once
# ---------------------------------------------------------------------------


def predict_matrix(
    learner: WeakLearner, spec: LearnerSpec, hyps: Any, X: jax.Array
) -> jax.Array:
    """Predictions of every hypothesis on one shard: [H, n] i32.

    The single place a round invokes ``learner.predict`` on the
    hypothesis space — every downstream quantity (error matrix, chosen
    mispredictions, weight update) is a reduction over this matrix.
    """
    return jax.vmap(lambda p: learner.predict(spec, p, X))(hyps)


def predict_tensor(
    learner: WeakLearner, spec: LearnerSpec, hyps: Any, X: jax.Array
) -> jax.Array:
    """Predictions of every hypothesis on every collaborator shard:
    X [C, n, d] -> [C, H, n] i32.  For a static hypothesis space
    (PreWeak.F) this is the setup-time prediction cache."""
    return jax.vmap(lambda Xi: predict_matrix(learner, spec, hyps, Xi))(X)


# ---------------------------------------------------------------------------
# Reduce many
# ---------------------------------------------------------------------------


def shard_errors(
    preds: jax.Array,  # [H, n] i32
    y: jax.Array,  # [n] i32
    w: jax.Array,  # [n] f32 (mask folded in)
    *,
    use_pallas: bool = False,
    **kw: Any,
) -> jax.Array:
    """eps[h] = sum_n w_n * 1[preds[h, n] != y_n] on one shard. [H] f32."""
    return ops.weighted_errors(preds, y, w, use_pallas=use_pallas, **kw)


def error_matrix(
    preds: jax.Array,  # [C, H, n] i32
    y: jax.Array,  # [C, n] i32
    w: jax.Array,  # [C, n] f32
    *,
    use_pallas: bool = False,
    **kw: Any,
) -> jax.Array:
    """eps[i, h] = weighted error of hypothesis h on collaborator i's
    shard (paper step 3), reduced from the materialised predictions."""
    return jax.vmap(
        lambda p, yi, wi: shard_errors(p, yi, wi, use_pallas=use_pallas, **kw)
    )(preds, y, w)


def chosen_mis(preds: jax.Array, y: jax.Array, c: jax.Array) -> jax.Array:
    """Misprediction vector of the chosen hypothesis: a row slice of the
    already-materialised predictions, NOT a second predict.

    preds [C, H, n] (or [H, n]), y [C, n] (or [n]), c scalar -> f32 mask.
    """
    rows = jnp.take(preds, c, axis=-2)  # [C, n] / [n]
    return (rows != y).astype(jnp.float32)


def update_weights(
    w: jax.Array,  # [C, n] (or [n]) f32
    mis: jax.Array,  # same shape, f32
    mask: jax.Array,  # same shape, f32
    alpha: jax.Array,  # scalar f32
    *,
    use_pallas: bool = False,
    renormalize: bool = True,
    **kw: Any,
) -> jax.Array:
    """Fused AdaBoost weight update ``w * exp(alpha*mis) * mask`` then
    global renormalisation (paper step 4 — the renormalisation is why
    weight norms are exchanged)."""
    flat = ops.weight_update(
        w.reshape(-1), mis.reshape(-1), mask.reshape(-1), alpha,
        use_pallas=use_pallas, **kw,
    ).reshape(w.shape)
    if not renormalize:
        return flat
    return flat / jnp.maximum(jnp.sum(flat), 1e-30)


# ---------------------------------------------------------------------------
# Masked (partial-participation) reductions — the elastic round's step 3/4
# ---------------------------------------------------------------------------
#
# An elastic round (fl/elastic.py) closes over a SUBSET of collaborators:
# ``part [C]`` is 1.0 for responders, 0.0 for absentees.  The helpers
# below are the masked twins of the reductions above.  With an all-ones
# ``part`` each helper computes the literal unmasked reduction too and
# selects it on ``jnp.all(part > 0)``, so the full-participation branch
# runs the lockstep ops.  XLA may still fuse those ops differently inside
# the two programs, so the contract the tests hold them to is
# CROSS_PATH_RTOL below, not identical bits.

# The cross-path equality contract.  Two paths that compute the same
# round — all-ones mask vs lockstep, fused vs per-process vs SPMD, Pallas
# kernel vs jnp oracle, CPU vs TPU — make the same discrete choices
# (argmin, chosen hypothesis, served vote away from exact ties) and agree
# on sample weights and alphas to this relative bound.  Float reductions
# may associate differently on each path (1-2 ulp per reduction in f32),
# so identical bits are not part of the contract.
CROSS_PATH_RTOL = 1e-5


def masked_error_sum(errs: jax.Array, part: jax.Array) -> jax.Array:
    """Global weighted error restricted to responding shards.

    errs [C, H], part [C] -> eps [H].  Absent collaborators' error rows
    are zeroed before the shard-axis sum: their samples simply are not
    in this round's federation."""
    masked = jnp.sum(jnp.where(part[:, None] > 0, errs, 0.0), axis=0)
    return jnp.where(jnp.all(part > 0), jnp.sum(errs, axis=0), masked)


def masked_argmin(eps: jax.Array, hyp_part: jax.Array) -> jax.Array:
    """argmin over the hypotheses of RESPONDING collaborators only
    (absent collaborators never uploaded theirs).  eps/hyp_part [H]."""
    masked = jnp.argmin(jnp.where(hyp_part > 0, eps, jnp.inf))
    return jnp.where(jnp.all(hyp_part > 0), jnp.argmin(eps), masked)


def participation_denom(weights: jax.Array, part: jax.Array) -> jax.Array:
    """Normaliser for a partial-participation weighted error.

    The sample weights are globally normalised over ALL shards, so an
    eps summed over responders only underestimates the error; dividing
    by the responders' weight mass renormalises it to a probability.
    Returns the literal 1.0 under full participation so the division is
    an IEEE-exact identity and the lockstep bits are preserved."""
    mass = jnp.sum(jnp.where(part[:, None] > 0, weights, 0.0))
    return jnp.where(jnp.all(part > 0), 1.0, jnp.maximum(mass, 1e-30))


def masked_update_weights(
    w: jax.Array,  # [C, n] f32
    mis: jax.Array,  # [C, n] f32
    mask: jax.Array,  # [C, n] f32
    part: jax.Array,  # [C] f32 — responders
    alpha: jax.Array,
    *,
    use_pallas: bool = False,
    **kw: Any,
) -> jax.Array:
    """Paper step 4 over responders only: absent collaborators' rows are
    FROZEN (they never saw the chosen hypothesis), but the global
    renormalisation still runs over every row — the weights stay one
    distribution over the whole federation, so a returning collaborator
    resumes with correctly-scaled weights."""
    upd = update_weights(
        w, mis, mask, alpha, use_pallas=use_pallas, renormalize=False, **kw
    )
    sel = jnp.where(part[:, None] > 0, upd, w)
    masked = sel / jnp.maximum(jnp.sum(sel), 1e-30)
    flat = upd.reshape(-1)
    lockstep = (flat / jnp.maximum(jnp.sum(flat), 1e-30)).reshape(w.shape)
    return jnp.where(jnp.all(part > 0), lockstep, masked)


def masked_member_prediction(
    learner: WeakLearner, spec: LearnerSpec, params_t: Any,
    cmask: jax.Array,  # [C] f32 — committee members present when appended
    X: jax.Array,
) -> jax.Array:
    """DistBoost.F committee vote with absent members' votes masked out
    (the committee slot always holds C member buffers; ``cmask`` records
    which of them actually participated in that round)."""
    preds = jax.vmap(lambda p: learner.predict(spec, p, X))(params_t)  # [C, n]
    oh = jax.nn.one_hot(preds, spec.n_classes)
    sub = jnp.sum(jnp.where(cmask[:, None, None] > 0, oh, 0.0), axis=0)
    return jnp.argmax(sub, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Incremental ensemble evaluation
# ---------------------------------------------------------------------------


class VoteTally(NamedTuple):
    """Running alpha-weighted vote tally over a fixed eval set.

    ``votes[n, K]`` accumulates the one-hot votes of ensemble members
    ``[0, counted)``; each refresh adds only the members appended since
    the last one — O(new members) predicts per eval instead of the O(T)
    full-ensemble re-prediction of ``boosting.ensemble_votes``.
    """

    votes: jax.Array  # [n, K] f32
    counted: jax.Array  # scalar i32 — ensemble members already tallied


def init_tally(n: int, n_classes: int) -> VoteTally:
    return VoteTally(
        votes=jnp.zeros((n, n_classes), jnp.float32),
        counted=jnp.zeros((), jnp.int32),
    )


def member_prediction(
    learner: WeakLearner, spec: LearnerSpec, params_t: Any, X: jax.Array,
    *, committee: bool = False,
) -> jax.Array:
    """One ensemble member's [n] class prediction — the single definition
    of the member vote rule, shared by full (``boosting.ensemble_votes``)
    and incremental (:func:`tally_new_votes`) evaluation."""
    if committee:  # DistBoost.F: majority vote of the committee first
        preds = jax.vmap(lambda p: learner.predict(spec, p, X))(params_t)
        sub = jnp.sum(jax.nn.one_hot(preds, spec.n_classes), axis=0)
        return jnp.argmax(sub, axis=-1).astype(jnp.int32)
    return learner.predict(spec, params_t, X)


def tally_new_votes(
    learner: WeakLearner,
    spec: LearnerSpec,
    ensemble,  # boosting.Ensemble (duck-typed: params/alpha/count)
    tally: VoteTally,
    X: jax.Array,
    *,
    committee: bool = False,
) -> VoteTally:
    """Fold members ``[tally.counted, ensemble.count)`` into the tally."""

    def add(t, votes):
        pred = member_prediction(
            learner, spec, _take_slot(ensemble.params, t), X, committee=committee
        )
        return votes + ensemble.alpha[t] * jax.nn.one_hot(pred, spec.n_classes)

    votes = jax.lax.fori_loop(tally.counted, ensemble.count, add, tally.votes)
    return VoteTally(votes=votes, counted=ensemble.count)


def tally_new_votes_masked(
    learner: WeakLearner,
    spec: LearnerSpec,
    ensemble,  # boosting.Ensemble of committee slots
    cmasks: jax.Array,  # [T, C] f32 — per-slot committee member masks
    tally: VoteTally,
    X: jax.Array,
) -> VoteTally:
    """:func:`tally_new_votes` for elastic DistBoost.F ensembles: each
    committee slot votes through its own membership mask.  With all-ones
    masks this is bit-for-bit ``tally_new_votes(committee=True)``."""

    def add(t, votes):
        pred = masked_member_prediction(
            learner, spec, _take_slot(ensemble.params, t), cmasks[t], X
        )
        return votes + ensemble.alpha[t] * jax.nn.one_hot(pred, spec.n_classes)

    votes = jax.lax.fori_loop(tally.counted, ensemble.count, add, tally.votes)
    return VoteTally(votes=votes, counted=ensemble.count)


def tally_predict(tally: VoteTally) -> jax.Array:
    return jnp.argmax(tally.votes, axis=-1).astype(jnp.int32)
