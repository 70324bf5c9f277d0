"""Heterogeneous-learner federations — per-collaborator model types.

MAFL's headline claim is that AdaBoost.F is *model-agnostic*: aggregation
only ever sees hypothesis **predictions**, so nothing in the protocol
requires collaborators to train the same model family.  This module makes
that claim executable: a federation may assign a different registered
``WeakLearner`` (with its own hyperparameters) to every collaborator, and
the boosting rounds, ensemble, artifact, and serving engine all operate
on the mixture.

Design
------
``HeterogeneousSpec`` is the static description: a tuple of per-group
``LearnerSpec``s (one per distinct learner configuration) plus an
``assignment`` mapping each collaborator to its group.  Everything
runtime-shaped derives from it:

  * **Grouped local fits** — collaborators sharing a learner are stacked
    and still run the batched binned fit (``boosting._local_fits`` with
    the group's slice of ONE round-key split, so grouping never changes
    which key a collaborator fits with).
  * **Cross-group voting** — each group's hypotheses are predicted on
    every shard (``scoring.predict_tensor``) and the per-group blocks
    concatenate into the same ``[C, H, n]`` prediction tensor the
    homogeneous rounds reduce, so the AdaBoost.F / DistBoost.F /
    PreWeak.F step-3/4 machinery (error matrix, argmin, weight update)
    never notices the mixture.
  * **Grouped ensemble** — the strong hypothesis is a tuple of per-group
    slot-buffer ``Ensemble``s (``HeteroEnsemble``).  Each round appends
    the winning hypothesis to its owner group only (a masked
    conditional write, since the winner is a traced quantity); votes
    commute, so evaluation is the sum of per-group vote tallies.

Bit-for-bit guarantee: with a single learner group the whole pipeline —
fits, prediction tensor, argmin, appends, weight updates, evaluation —
reduces to the exact operations of the homogeneous path (identity
gathers, single-element concatenations, always-true conditional writes),
so a ``HeterogeneousSpec`` with one entry is bit-for-bit the existing
``LearnerSpec`` federation.  Regression-tested in tests/test_hetero.py.

Heterogeneity requires the fused round path: the interpreted simulation
scores a single stacked hypothesis pytree and the SPMD ``fl/sharded.py``
round is one program for every device, neither of which admits
per-collaborator model structure.  ``Federation`` validates this.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scoring
from repro.core.boosting import (
    BoostState,
    Ensemble,
    _local_fits,
    _preweak_local_space,
    _samme_alpha,
    _set_slot,
    _take_slot,
    ensemble_votes,
    init_ensemble,
    run_stages,
)
from repro.learners.base import LearnerSpec, WeakLearner, get_learner

# The strong hypothesis of a heterogeneous federation: one slot-buffer
# Ensemble per learner group.  A plain tuple — serialization, signatures
# and jit all treat it as an ordinary pytree.
HeteroEnsemble = Tuple[Ensemble, ...]


@dataclasses.dataclass(frozen=True)
class HeterogeneousSpec:
    """Per-collaborator learner assignment for one federation.

    ``specs[g]`` describes learner group ``g`` (registry key + problem
    geometry + hyperparameters); ``assignment[i]`` names collaborator
    ``i``'s group.  All groups must share ``n_features``/``n_classes``
    (one learning problem, many model families) and every group must own
    at least one collaborator.
    """

    specs: Tuple[LearnerSpec, ...]
    assignment: Tuple[int, ...]

    def __post_init__(self):
        if not self.specs:
            raise ValueError("HeterogeneousSpec needs at least one learner group")
        if not self.assignment:
            raise ValueError("HeterogeneousSpec needs at least one collaborator")
        nf = {s.n_features for s in self.specs}
        nc = {s.n_classes for s in self.specs}
        if len(nf) != 1 or len(nc) != 1:
            raise ValueError(
                f"all learner groups must share the problem geometry; "
                f"got n_features={sorted(nf)}, n_classes={sorted(nc)}"
            )
        bad = [g for g in self.assignment if not 0 <= g < len(self.specs)]
        if bad:
            raise ValueError(f"assignment references unknown groups {sorted(set(bad))}")
        unused = set(range(len(self.specs))) - set(self.assignment)
        if unused:
            raise ValueError(f"learner groups {sorted(unused)} have no collaborators")

    # -- geometry ----------------------------------------------------------
    @property
    def n_features(self) -> int:
        return self.specs[0].n_features

    @property
    def n_classes(self) -> int:
        return self.specs[0].n_classes

    @property
    def n_collaborators(self) -> int:
        return len(self.assignment)

    @property
    def n_groups(self) -> int:
        return len(self.specs)

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(s.name for s in self.specs)

    def members(self, g: int) -> Tuple[int, ...]:
        """Collaborator indices of group ``g``, ascending."""
        return tuple(i for i, gi in enumerate(self.assignment) if gi == g)

    # -- construction ------------------------------------------------------
    @classmethod
    def cycle(
        cls,
        names: Sequence[str],
        n_collaborators: int,
        n_features: int,
        n_classes: int,
        hparams: Optional[Dict[str, Dict[str, Any]]] = None,
    ) -> "HeterogeneousSpec":
        """Cycle learner registry keys across collaborators: collaborator
        ``i`` gets ``names[i % len(names)]``.  ``hparams`` maps a registry
        key to that learner's hyperparameters.  Identical (name, hparams)
        entries collapse into one group — ``cycle(["decision_tree"], C)``
        is the single-group spec that is bit-for-bit the homogeneous
        path."""
        if not names:
            raise ValueError("cycle() needs at least one learner name")
        hparams = hparams or {}
        groups: List[LearnerSpec] = []
        keyed: Dict[str, int] = {}  # (name, canonical hparams) -> group index
        assignment = []
        for i in range(n_collaborators):
            name = names[i % len(names)]
            hp = dict(hparams.get(name, {}))
            k = f"{name}|{json.dumps(hp, sort_keys=True)}"
            if k not in keyed:
                keyed[k] = len(groups)
                groups.append(LearnerSpec(name, n_features, n_classes, hp))
            assignment.append(keyed[k])
        return cls(specs=tuple(groups), assignment=tuple(assignment))


def resolve(hspec: HeterogeneousSpec) -> Tuple[WeakLearner, ...]:
    """Registry lookup for every group (raises KeyError on unknown keys)."""
    return tuple(get_learner(s.name) for s in hspec.specs)


def group_committee_sizes(
    hspec: HeterogeneousSpec, committee: bool
) -> Tuple[Optional[int], ...]:
    """DistBoost.F stores each round's full committee; group ``g`` holds
    its ``len(members(g))`` seats of it."""
    if not committee:
        return (None,) * hspec.n_groups
    return tuple(len(hspec.members(g)) for g in range(hspec.n_groups))


def hetero_count(hens: HeteroEnsemble, *, committee: bool = False) -> int:
    """Used member count of a heterogeneous ensemble (host-side).

    Plain ensembles: the winners are spread over the groups, so the
    total is the sum of group counts.  Committee ensembles: every round
    appends one seat-block to EVERY group, so all counts are equal and
    the member count is any one of them."""
    if committee:
        return int(hens[0].count)
    return sum(int(e.count) for e in hens)


# ---------------------------------------------------------------------------
# Static index maps (host-side numpy; appear as constants in jitted rounds)
# ---------------------------------------------------------------------------


def _member_index(hspec: HeterogeneousSpec) -> List[np.ndarray]:
    return [np.asarray(hspec.members(g), np.int32) for g in range(hspec.n_groups)]


def _hyp_maps(hspec: HeterogeneousSpec, per_member: int = 1):
    """Maps over the group-blocked global hypothesis order.

    The global order lists group 0's hypotheses (its members ascending,
    ``per_member`` each — PreWeak.F spaces carry T per member), then
    group 1's, ...  Returns (owner, local, collab): hypothesis j belongs
    to group ``owner[j]`` at group-local slot ``local[j]``, trained by
    collaborator ``collab[j]``."""
    owner, local, collab = [], [], []
    for g in range(hspec.n_groups):
        m = hspec.members(g)
        cnt = len(m) * per_member
        owner.append(np.full(cnt, g, np.int32))
        local.append(np.arange(cnt, dtype=np.int32))
        collab.append(np.repeat(np.asarray(m, np.int32), per_member))
    return (np.concatenate(owner), np.concatenate(local), np.concatenate(collab))


# ---------------------------------------------------------------------------
# State init
# ---------------------------------------------------------------------------


def init_hetero_ensemble(
    hspec: HeterogeneousSpec, T: int, key: jax.Array, *, committee: bool = False
) -> HeteroEnsemble:
    """Per-group slot buffers, each with the FULL capacity ``T`` (any
    group can win every round; buffers are weak-learner sized)."""
    sizes = group_committee_sizes(hspec, committee)
    return tuple(
        init_ensemble(learner, spec, T, key, committee_size=cs)
        for learner, spec, cs in zip(resolve(hspec), hspec.specs, sizes)
    )


def init_hetero_boost_state(
    hspec: HeterogeneousSpec,
    T: int,
    mask: jax.Array,  # [C, n]
    key: jax.Array,
    *,
    committee: bool = False,
    X: Optional[jax.Array] = None,  # [C, n, d] — enables per-group fit caches
) -> BoostState:
    """The heterogeneous analogue of ``boosting.init_boost_state``: the
    ensemble is a group tuple and ``fit_cache`` holds one per-group cache
    pytree (each group precomputes over its own members' shards)."""
    k1, k2 = jax.random.split(key)
    w = mask / jnp.maximum(jnp.sum(mask), 1.0)
    caches = None
    if X is not None:
        idx = _member_index(hspec)
        caches = tuple(
            jax.vmap(lambda Xi, spec=spec, learner=learner: learner.precompute(spec, Xi))(
                X[idx[g]]
            )
            if learner.precompute is not None and learner.fit_cached is not None
            else None
            for g, (learner, spec) in enumerate(zip(resolve(hspec), hspec.specs))
        )
    return BoostState(
        ensemble=init_hetero_ensemble(hspec, T, k1, committee=committee),
        weights=w.astype(jnp.float32),
        key=k2,
        fit_cache=caches,
    )


# ---------------------------------------------------------------------------
# Grouped round machinery
# ---------------------------------------------------------------------------


def _grouped_local_fits(
    hspec, learners, w, X, y, key, caches,
    *, batched=True, use_pallas=False, block_s=None, block_d=None,
) -> List[Any]:
    """Paper step 2 under heterogeneity: ONE key split for all C
    collaborators, then each group batch-fits its members' slice (the
    PR-3 batched binned fit still applies within every group).  Returns
    the per-group ``[C_g, ...]`` hypothesis stacks."""
    keys = jax.random.split(key, hspec.n_collaborators)
    idx = _member_index(hspec)
    out = []
    for g, (learner, spec) in enumerate(zip(learners, hspec.specs)):
        i = idx[g]
        out.append(
            _local_fits(
                learner, spec, w[i], X[i], y[i], None,
                caches[g] if caches is not None else None,
                batched=batched, use_pallas=use_pallas,
                block_s=block_s, block_d=block_d,
                keys=keys[i],
            )
        )
    return out


def _grouped_predict_tensor(hspec, learners, hyps: Sequence[Any], X) -> jax.Array:
    """The cross-group ``[C, H, n]`` prediction tensor (paper step 3):
    every group's hypotheses predicted on EVERY collaborator shard, the
    per-group blocks concatenated along the hypothesis axis in the
    canonical group-blocked order of :func:`_hyp_maps`."""
    parts = [
        scoring.predict_tensor(learner, spec, hyps[g], X)
        for g, (learner, spec) in enumerate(zip(learners, hspec.specs))
    ]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _append_chosen(
    hens: HeteroEnsemble,
    sources: Sequence[Any],
    owner: np.ndarray,
    local: np.ndarray,
    c: jax.Array,
    alpha,
) -> HeteroEnsemble:
    """Append hypothesis ``c`` (a traced index into the global order
    described by ``owner``/``local``) to its owner group only.  The
    winner is data-dependent, so every group performs the write and
    keeps it only where it won — with one group the mask is constant
    true and this is exactly the homogeneous unconditional append."""
    owner_j = jnp.asarray(owner)
    local_j = jnp.asarray(local)
    out = []
    for g, ens_g in enumerate(hens):
        won = owner_j[c] == g
        idx = jnp.where(won, local_j[c], 0)  # clamp losers to a valid slot
        appended = Ensemble(
            params=_set_slot(ens_g.params, ens_g.count, _take_slot(sources[g], idx)),
            alpha=ens_g.alpha.at[ens_g.count].set(alpha),
            count=ens_g.count + 1,
        )
        out.append(jax.tree.map(lambda a, b: jnp.where(won, a, b), appended, ens_g))
    return tuple(out)


def _committee_tally(learners, hspec, params_by_group, X) -> jax.Array:
    """[n, K] one-hot vote tally of one mixed committee whose group
    ``g`` seats are ``params_by_group[g]`` (leading dim = group size)."""
    tally = None
    for g, (learner, spec) in enumerate(zip(learners, hspec.specs)):
        preds = jax.vmap(lambda p, learner=learner, spec=spec: learner.predict(spec, p, X))(
            params_by_group[g]
        )  # [C_g, n]
        t = jnp.sum(jax.nn.one_hot(preds, spec.n_classes), axis=0)
        tally = t if tally is None else tally + t
    return tally


# ---------------------------------------------------------------------------
# Rounds — same step structure as core/boosting.py, grouped
# ---------------------------------------------------------------------------


def hetero_adaboost_f_stages(
    hspec: HeterogeneousSpec,
    *,
    use_pallas: bool = False,
    batched_fit: bool = True,
    block_s: Optional[int] = None,
    block_d: Optional[int] = None,
):
    """Grouped AdaBoost.F round as named stages (see
    :func:`repro.core.boosting.run_stages`)."""
    learners = resolve(hspec)

    def fit(state, carry, X, y, mask):
        key, kfit = jax.random.split(state.key)
        hyps = _grouped_local_fits(
            hspec, learners, state.weights, X, y, kfit, state.fit_cache,
            batched=batched_fit, use_pallas=use_pallas,
            block_s=block_s, block_d=block_d,
        )
        return BoostState(state.ensemble, state.weights, key, state.fit_cache), {
            "hyps": hyps
        }

    def score(state, carry, X, y, mask):
        preds = _grouped_predict_tensor(hspec, learners, carry["hyps"], X)  # [C, H, n]
        errs = scoring.error_matrix(preds, y, state.weights, use_pallas=use_pallas)
        return state, {**carry, "preds": preds, "errs": errs}

    def aggregate(state, carry, X, y, mask):
        hyps, preds, errs = carry["hyps"], carry["preds"], carry["errs"]
        eps = jnp.sum(errs, axis=0)
        c = jnp.argmin(eps)
        alpha = _samme_alpha(eps[c], hspec.n_classes)

        owner, local, collab = _hyp_maps(hspec)
        ens = _append_chosen(state.ensemble, hyps, owner, local, c, alpha)
        mis = scoring.chosen_mis(preds, y, c)
        w = scoring.update_weights(state.weights, mis, mask, alpha, use_pallas=use_pallas)
        metrics = {
            "epsilon": eps[c],
            "alpha": alpha,
            "chosen": jnp.asarray(collab)[c].astype(jnp.int32),
        }
        return BoostState(ens, w, state.key, state.fit_cache), {"metrics": metrics}

    return [("fit", fit), ("score", score), ("aggregate", aggregate)]


def hetero_adaboost_f_round(
    hspec: HeterogeneousSpec,
    state: BoostState,
    X: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    *,
    use_pallas: bool = False,
    batched_fit: bool = True,
    block_s: Optional[int] = None,
    block_d: Optional[int] = None,
):
    return run_stages(
        hetero_adaboost_f_stages(
            hspec, use_pallas=use_pallas, batched_fit=batched_fit,
            block_s=block_s, block_d=block_d,
        ),
        state, X, y, mask,
    )


def hetero_distboost_f_stages(
    hspec, *,
    use_pallas: bool = False, batched_fit: bool = True,
    block_s: Optional[int] = None, block_d: Optional[int] = None,
):
    """Grouped DistBoost.F round as named stages."""
    learners = resolve(hspec)

    def fit(state, carry, X, y, mask):
        key, kfit = jax.random.split(state.key)
        committees = _grouped_local_fits(
            hspec, learners, state.weights, X, y, kfit, state.fit_cache,
            batched=batched_fit, use_pallas=use_pallas,
            block_s=block_s, block_d=block_d,
        )
        return BoostState(state.ensemble, state.weights, key, state.fit_cache), {
            "committees": committees
        }

    def score(state, carry, X, y, mask):
        committees = carry["committees"]

        def mis_one(Xi, yi):
            tally = _committee_tally(learners, hspec, committees, Xi)
            pred = jnp.argmax(tally, axis=-1).astype(jnp.int32)
            return (pred != yi).astype(jnp.float32)

        mis = jax.vmap(mis_one)(X, y)  # [C, n] — the round's ONLY predict pass
        return state, {**carry, "mis": mis}

    def aggregate(state, carry, X, y, mask):
        committees, mis = carry["committees"], carry["mis"]
        w = state.weights
        eps = jnp.sum(w * mis)
        alpha = _samme_alpha(eps, hspec.n_classes)

        # the round hypothesis is the WHOLE mixed committee: every group
        # appends its seat block, counts advance in lockstep
        ens = tuple(
            Ensemble(
                params=_set_slot(e.params, e.count, committees[g]),
                alpha=e.alpha.at[e.count].set(alpha),
                count=e.count + 1,
            )
            for g, e in enumerate(state.ensemble)
        )
        w = scoring.update_weights(w, mis, mask, alpha, use_pallas=use_pallas)
        metrics = {"epsilon": eps, "alpha": alpha, "chosen": jnp.zeros((), jnp.int32)}
        return BoostState(ens, w, state.key, state.fit_cache), {"metrics": metrics}

    return [("fit", fit), ("score", score), ("aggregate", aggregate)]


def hetero_distboost_f_round(
    hspec, state, X, y, mask, *,
    use_pallas: bool = False, batched_fit: bool = True,
    block_s: Optional[int] = None, block_d: Optional[int] = None,
):
    return run_stages(
        hetero_distboost_f_stages(
            hspec, use_pallas=use_pallas, batched_fit=batched_fit,
            block_s=block_s, block_d=block_d,
        ),
        state, X, y, mask,
    )


def hetero_preweak_f_setup(hspec, state, X, y, mask, T: int):
    """Grouped PreWeak.F steps 1+2: every collaborator runs T rounds of
    LOCAL AdaBoost with its OWN learner; group ``g`` owns a flat
    ``[C_g * T, ...]`` block of the federation's hypothesis space."""
    learners = resolve(hspec)
    C = hspec.n_collaborators
    keys = jax.random.split(state.key, C + 1)
    idx = _member_index(hspec)
    spaces = []
    for g, (learner, spec) in enumerate(zip(learners, hspec.specs)):
        i = idx[g]
        cache_g = state.fit_cache[g] if state.fit_cache is not None else None
        spaces.append(
            _preweak_local_space(
                learner, spec, X[i], y[i], mask[i], keys[i], cache_g, T
            )
        )
    return tuple(spaces), BoostState(
        state.ensemble, state.weights, keys[-1], state.fit_cache
    )


def hetero_preweak_f_predictions(hspec, spaces, X) -> jax.Array:
    """Setup-time ``[C, sum_g C_g*T, n]`` prediction cache over the
    static mixed hypothesis space (group-blocked order)."""
    return _grouped_predict_tensor(hspec, resolve(hspec), spaces, X)


def hetero_preweak_f_stages(
    hspec, spaces, *,
    pred_cache: Optional[jax.Array] = None, use_pallas: bool = False,
):
    """Grouped PreWeak.F round as named stages (no fit — the mixed
    hypothesis space is pre-trained at setup)."""

    def score(state, carry, X, y, mask):
        preds = (
            pred_cache
            if pred_cache is not None
            else hetero_preweak_f_predictions(hspec, spaces, X)
        )
        errs = scoring.error_matrix(preds, y, state.weights, use_pallas=use_pallas)
        return state, {"preds": preds, "errs": errs}

    def aggregate(state, carry, X, y, mask):
        preds, errs = carry["preds"], carry["errs"]
        eps = jnp.sum(errs, axis=0)
        c = jnp.argmin(eps)
        alpha = _samme_alpha(eps[c], hspec.n_classes)

        T = preds.shape[1] // hspec.n_collaborators
        owner, local, _ = _hyp_maps(hspec, per_member=T)
        ens = _append_chosen(state.ensemble, spaces, owner, local, c, alpha)
        mis = scoring.chosen_mis(preds, y, c)
        w = scoring.update_weights(state.weights, mis, mask, alpha, use_pallas=use_pallas)
        metrics = {"epsilon": eps[c], "alpha": alpha, "chosen": c.astype(jnp.int32)}
        return BoostState(ens, w, state.key, state.fit_cache), {"metrics": metrics}

    return [("score", score), ("aggregate", aggregate)]


def hetero_preweak_f_round(
    hspec, state, spaces, X, y, mask, *,
    pred_cache: Optional[jax.Array] = None, use_pallas: bool = False,
):
    return run_stages(
        hetero_preweak_f_stages(
            hspec, spaces, pred_cache=pred_cache, use_pallas=use_pallas
        ),
        state, X, y, mask,
    )


def hetero_bagging_stages(
    hspec, *,
    use_pallas: bool = False, batched_fit: bool = True,
    block_s: Optional[int] = None, block_d: Optional[int] = None,
):
    """Grouped federated-bagging round as named stages (no score — the
    scoring reduction is skipped entirely)."""
    learners = resolve(hspec)

    def fit(state, carry, X, y, mask):
        key, kfit, kpick = jax.random.split(state.key, 3)
        w = mask / jnp.maximum(jnp.sum(mask, axis=1, keepdims=True), 1.0)  # local-uniform
        hyps = _grouped_local_fits(
            hspec, learners, w, X, y, kfit, state.fit_cache,
            batched=batched_fit, use_pallas=use_pallas,
            block_s=block_s, block_d=block_d,
        )
        return BoostState(state.ensemble, state.weights, key, state.fit_cache), {
            "hyps": hyps, "kpick": kpick
        }

    def aggregate(state, carry, X, y, mask):
        hyps, kpick = carry["hyps"], carry["kpick"]
        c = jax.random.randint(kpick, (), 0, hspec.n_collaborators)  # collaborator index
        # collaborator -> (owner group, group-local rank): the collaborator-
        # indexed view of the _hyp_maps tables
        owner = np.asarray(hspec.assignment, np.int32)
        rank = np.zeros(hspec.n_collaborators, np.int32)
        for g in range(hspec.n_groups):
            for r, i in enumerate(hspec.members(g)):
                rank[i] = r
        ens = _append_chosen(state.ensemble, hyps, owner, rank, c, 1.0)
        metrics = {
            "epsilon": jnp.zeros(()), "alpha": jnp.ones(()),
            "chosen": c.astype(jnp.int32),
        }
        return BoostState(ens, state.weights, state.key, state.fit_cache), {
            "metrics": metrics
        }

    return [("fit", fit), ("aggregate", aggregate)]


def hetero_bagging_round(
    hspec, state, X, y, mask, *,
    use_pallas: bool = False, batched_fit: bool = True,
    block_s: Optional[int] = None, block_d: Optional[int] = None,
):
    return run_stages(
        hetero_bagging_stages(
            hspec, use_pallas=use_pallas, batched_fit=batched_fit,
            block_s=block_s, block_d=block_d,
        ),
        state, X, y, mask,
    )


HETERO_ROUND_FNS = {
    "adaboost_f": hetero_adaboost_f_round,
    "distboost_f": hetero_distboost_f_round,
    "bagging": hetero_bagging_round,
}


# ---------------------------------------------------------------------------
# Evaluation — votes commute, so the mixture is a sum of group tallies
# ---------------------------------------------------------------------------


def hetero_ensemble_votes(
    hspec: HeterogeneousSpec, hens: HeteroEnsemble, X: jax.Array,
    *, committee: bool = False,
) -> jax.Array:
    """Alpha-weighted vote tally [n, K] of a mixed ensemble.

    Plain members vote within their group, and group tallies add.
    Committee members span every group, so their majority vote must be
    taken over the cross-group seat tally BEFORE the alpha weighting —
    group counts/alphas advance in lockstep for committees, so group 0's
    are authoritative."""
    X = jnp.asarray(X)  # member predicts index X with traced scalars
    learners = resolve(hspec)
    if committee:
        T = hens[0].alpha.shape[0]

        def member(t):
            tally = _committee_tally(
                learners, hspec, [_take_slot(e.params, t) for e in hens], X
            )
            return jnp.argmax(tally, axis=-1).astype(jnp.int32)

        preds = jax.vmap(member)(jnp.arange(T))  # [T, n]
        used = (jnp.arange(T) < hens[0].count).astype(jnp.float32) * hens[0].alpha
        onehot = jax.nn.one_hot(preds, hspec.n_classes)
        return jnp.einsum("t,tnk->nk", used, onehot)

    votes = None
    for g, (learner, spec) in enumerate(zip(learners, hspec.specs)):
        v = ensemble_votes(learner, spec, hens[g], X)
        votes = v if votes is None else votes + v
    return votes


def hetero_strong_predict(
    hspec, hens, X, *, committee: bool = False
) -> jax.Array:
    return jnp.argmax(
        hetero_ensemble_votes(hspec, hens, X, committee=committee), axis=-1
    )


def init_hetero_tally(
    hspec: HeterogeneousSpec, n: int, *, committee: bool = False
) -> Tuple[scoring.VoteTally, ...]:
    """Incremental-eval state: one running tally per group (committee
    ensembles fold cross-group, so they keep a single tally)."""
    n_tallies = 1 if committee else hspec.n_groups
    return tuple(scoring.init_tally(n, hspec.n_classes) for _ in range(n_tallies))


def hetero_tally_new_votes(
    hspec: HeterogeneousSpec,
    hens: HeteroEnsemble,
    tallies: Tuple[scoring.VoteTally, ...],
    X: jax.Array,
    *,
    committee: bool = False,
) -> Tuple[scoring.VoteTally, ...]:
    """Fold only the members appended since the last eval — the
    heterogeneous analogue of ``scoring.tally_new_votes`` (per-group
    counts move independently for plain ensembles, in lockstep for
    committees)."""
    learners = resolve(hspec)
    if committee:
        (tl,) = tallies

        def add(t, votes):
            tally = _committee_tally(
                learners, hspec, [_take_slot(e.params, t) for e in hens], X
            )
            pred = jnp.argmax(tally, axis=-1).astype(jnp.int32)
            return votes + hens[0].alpha[t] * jax.nn.one_hot(pred, hspec.n_classes)

        votes = jax.lax.fori_loop(tl.counted, hens[0].count, add, tl.votes)
        return (scoring.VoteTally(votes=votes, counted=hens[0].count),)
    return tuple(
        scoring.tally_new_votes(learner, spec, hens[g], tallies[g], X)
        for g, (learner, spec) in enumerate(zip(learners, hspec.specs))
    )


def hetero_tally_predict(tallies: Tuple[scoring.VoteTally, ...]) -> jax.Array:
    votes = tallies[0].votes
    for t in tallies[1:]:
        votes = votes + t.votes
    return jnp.argmax(votes, axis=-1).astype(jnp.int32)
