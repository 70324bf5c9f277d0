"""Elastic asynchronous federation rounds — partial participation,
straggler deadlines, staleness-discounted late merges, and membership
churn for the MAFL boosting algorithms.

``Federation.run`` is a synchronous lockstep loop: one slow or dead
collaborator stalls every round, which is exactly the gap the FL surveys
flag between reproductions and production deployments (PAPERS.md:
2104.14362 §async FL, 2504.17703 on partial participation).  This module
turns the round loop into an event-driven scheduler, modeled on the
serving side's ``serve/scheduler.py::DeadlineScheduler``:

  * **Participation masks.**  Every step-3/4 reduction takes a ``part
    [C]`` responder mask: AdaBoost.F's argmin runs over responders'
    hypotheses only, error sums and weight-mass normalisers run over
    responders' shards only, and absent collaborators' weight rows are
    frozen (``core/scoring.py`` masked helpers).  With an all-ones mask
    every round is BIT-FOR-BIT the lockstep round — the equivalence
    contract ``tests/test_elastic.py`` pins for all four algorithms.
  * **Straggler deadline.**  A round closes over whoever answered within
    ``ParticipationPolicy.deadline_s`` (``None`` = wait for everyone,
    i.e. lockstep).  ``virtual`` mode derives arrival times from the
    ``FaultPlan`` deterministically (tests); ``realtime`` mode waits on
    an ``_ArrivalBoard`` condition variable fed by timers (benches).
  * **Staleness-discounted late merges.**  A hypothesis fitted for round
    ``r`` that arrives at round ``r' <= r + max_staleness`` is scored
    against the CURRENT weights over the current responders' shards and
    appended with ``alpha = gamma**(r'-r) * samme_alpha(eps_now)`` — no
    weight update, so the discount is monotone in lateness by
    construction.  Late merges apply to the hypothesis-upload algorithms
    (adaboost_f, bagging); DistBoost.F's round artifact is the whole
    committee and PreWeak.F pre-ships its space, so for those a late
    collaborator is simply masked out of the round.
  * **Membership churn.**  Collaborators join/leave mid-federation via
    the policy's ``joins``/``leaves`` windows.  The data layout stays
    the collaborator-stacked ``[C, n, d]`` slot buffer (the same
    pre-allocated-capacity idiom as ``core/hetero.py``'s grouped slot
    buffers): membership gates participation, never shapes, so nothing
    recompiles when the federation grows or shrinks.

``FaultPlan`` is the deterministic, seed-driven injection layer (delay /
drop / kill / flaky-rejoin schedules per collaborator) consumed by the
chaos tests and ``benchmarks/bench_elastic.py``.  The multi-process
mirror with real dead-process eviction lives in ``fl/elastic_dist.py``.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import boosting, scoring
from repro.core.boosting import BoostState, Ensemble, _samme_alpha, _set_slot, _take_slot
from repro.core.metrics import f1_macro
from repro.core.plan import Plan
from repro.core.serialization import wire_size
from repro.learners.base import LearnerSpec, get_learner
from repro.obs import metrics as obs_metrics, trace

# Families shared with fl/federation.py (the registry dedupes by name) plus
# the elastic-only dropout/late-merge counters — see docs/ARCHITECTURE.md,
# "Observability" and "Elastic runtime".
_M_ROUNDS = obs_metrics.counter(
    "mafl_federation_rounds_total", "Federated rounds completed (all paths)."
)
_M_COMM = obs_metrics.counter(
    "mafl_federation_comm_bytes_total",
    "Wire bytes between collaborators and the aggregator: measured on the "
    "interpreted path, modelled from artifact shapes on the fused path.",
)
_M_ROUND_SECONDS = obs_metrics.histogram(
    "mafl_federation_round_seconds",
    "Wall-clock seconds per federated round (history-row averages).",
)
_M_DROPOUT = obs_metrics.counter(
    "mafl_federation_dropout_total",
    "Collaborator-rounds lost to faults, by reason: deadline (missed the "
    "straggler cutoff), drop (update never arrived), dead (process/"
    "collaborator killed), stale (arrived past max_staleness).",
    labels=("reason",),
)
_M_LATE_MERGES = obs_metrics.counter(
    "mafl_federation_dropout_late_merges_total",
    "Straggler hypotheses merged after their round closed, with a "
    "staleness-discounted alpha.",
)


def staleness_discount(gamma: float, lateness: int) -> float:
    """Discount applied to a late hypothesis's alpha: ``gamma**lateness``.

    Monotone non-increasing in lateness for ``gamma`` in (0, 1] — the
    contract the property tests pin (a hypothesis merged two rounds late
    never outweighs the same hypothesis merged one round late)."""
    if not 0.0 < gamma <= 1.0:
        raise ValueError(f"staleness_gamma must be in (0, 1], got {gamma}")
    if lateness < 0:
        raise ValueError(f"lateness must be >= 0, got {lateness}")
    return gamma**lateness


# ---------------------------------------------------------------------------
# Fault injection — deterministic, seed-driven
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """Seed-driven per-collaborator fault schedule.

    All randomness comes from ``np.random.default_rng(seed)`` at
    :meth:`schedule` time, so the same plan produces the same faults in
    every process that evaluates it — the chaos tests and the
    multi-process runtime (``fl/elastic_dist.py``) rely on that.

      * ``delay_p`` / ``delay_range_s`` — with probability ``delay_p`` a
        collaborator's round-``r`` upload is delayed by a uniform draw
        from ``delay_range_s`` seconds (a straggler);
      * ``drop_p``  — the upload never arrives at all;
      * ``kills``   — ``(collaborator, round)``: permanent death at the
        start of that round (the process exits in distributed mode);
      * ``flaky``   — ``(collaborator, off_round, rejoin_round)``: offline
        for ``[off_round, rejoin_round)`` then rejoins.
    """

    seed: int = 0
    delay_p: float = 0.0
    delay_range_s: Tuple[float, float] = (0.0, 0.0)
    drop_p: float = 0.0
    kills: Tuple[Tuple[int, int], ...] = ()
    flaky: Tuple[Tuple[int, int, int], ...] = ()

    def schedule(self, rounds: int, n_collaborators: int) -> "FaultSchedule":
        C = n_collaborators
        rng = np.random.default_rng(self.seed)
        delayed = rng.random((rounds, C)) < self.delay_p
        delay = np.zeros((rounds, C))
        lo, hi = self.delay_range_s
        delay[delayed] = rng.uniform(lo, hi, size=int(delayed.sum()))
        drop = rng.random((rounds, C)) < self.drop_p
        alive = np.ones((rounds, C), bool)
        for i, r0 in self.kills:
            alive[max(r0, 0):, i] = True if r0 >= rounds else False
            if r0 < rounds:
                alive[r0:, i] = False
        offline = np.zeros((rounds, C), bool)
        for i, a, b in self.flaky:
            offline[max(a, 0):max(b, 0), i] = True
        return FaultSchedule(delay=delay, drop=drop, alive=alive, offline=offline)


@dataclasses.dataclass(frozen=True)
class FaultSchedule:
    """Materialised per-(round, collaborator) fault arrays."""

    delay: np.ndarray  # [R, C] f64 seconds
    drop: np.ndarray  # [R, C] bool
    alive: np.ndarray  # [R, C] bool
    offline: np.ndarray  # [R, C] bool


# ---------------------------------------------------------------------------
# Participation policy
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParticipationPolicy:
    """How an elastic round decides who it closes over.

      * ``deadline_s``     — straggler deadline per round; ``None`` waits
        for every active collaborator (lockstep semantics — with no
        faults this is bit-for-bit ``Federation.run``);
      * ``min_responders`` — a round never closes over fewer responders:
        the deadline stretches to the fastest ``min_responders`` arrivals;
      * ``staleness_gamma`` / ``max_staleness`` / ``late_merge`` — the
        late-arrival contract (see :func:`staleness_discount`);
      * ``joins`` / ``leaves`` — ``(collaborator, round)`` membership
        windows: a collaborator participates in rounds
        ``[join, leave)``;
      * ``realtime``       — wall-clock arrival waiting on the
        ``_ArrivalBoard`` (benches) instead of the deterministic virtual
        clock derived from the ``FaultPlan`` (tests).
    """

    deadline_s: Optional[float] = None
    min_responders: int = 1
    staleness_gamma: float = 0.5
    max_staleness: int = 2
    late_merge: bool = True
    joins: Tuple[Tuple[int, int], ...] = ()
    leaves: Tuple[Tuple[int, int], ...] = ()
    realtime: bool = False

    def validate(self) -> None:
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError(f"deadline_s must be positive or None, got {self.deadline_s}")
        if self.min_responders < 1:
            raise ValueError(f"min_responders must be >= 1, got {self.min_responders}")
        if not 0.0 < self.staleness_gamma <= 1.0:
            raise ValueError(f"staleness_gamma must be in (0, 1], got {self.staleness_gamma}")
        if self.max_staleness < 0:
            raise ValueError(f"max_staleness must be >= 0, got {self.max_staleness}")

    def membership(self, rounds: int, n_collaborators: int) -> np.ndarray:
        """[R, C] bool — which collaborators are members at each round."""
        m = np.ones((rounds, n_collaborators), bool)
        for i, r0 in self.joins:
            m[: min(max(r0, 0), rounds), i] = False
        for i, r0 in self.leaves:
            m[min(max(r0, 0), rounds):, i] = False
        return m


# ---------------------------------------------------------------------------
# Masked round stages — the lockstep stages with `part` threaded through
# ---------------------------------------------------------------------------


def run_elastic_stages(stages, state: BoostState, X, y, mask, part):
    """:func:`boosting.run_stages` with the responder mask threaded
    through; each stage runs under its ``jax.named_scope`` and the same
    ``optimization_barrier`` seals every stage boundary so the masked
    round compiles to the same per-stage numeric programs as the
    lockstep round (the all-ones equivalence contract).

    Returns ``(state, metrics, round_hyps)`` — ``round_hyps`` is the
    ``[C, ...]`` fit output for algorithms whose late merges need it
    (adaboost_f / bagging), else ``None``."""
    carry: Dict[str, Any] = {}
    for name, fn in stages:
        with jax.named_scope(name):
            state, carry = fn(state, carry, X, y, mask, part)
        state, carry = jax.lax.optimization_barrier((state, carry))
    return state, carry["metrics"], carry.get("hyps")


def elastic_adaboost_f_stages(
    learner, spec, *,
    use_pallas: bool = False, batched_fit: bool = True,
    block_s: int | None = None, block_d: int | None = None,
):
    """AdaBoost.F with partial participation: argmin over responders'
    hypotheses and shards only; absentees' weight rows freeze."""

    def fit(state, carry, X, y, mask, part):
        key, kfit = jax.random.split(state.key)
        # all C rows are fitted (the batched program is shape-static and
        # the PRNG schedule must not depend on who responds); `part`
        # masks the outputs downstream, never the computation
        hyps = boosting._local_fits(
            learner, spec, state.weights, X, y, kfit, state.fit_cache,
            batched=batched_fit, use_pallas=use_pallas,
            block_s=block_s, block_d=block_d,
        )
        return BoostState(state.ensemble, state.weights, key, state.fit_cache), {
            "hyps": hyps
        }

    def score(state, carry, X, y, mask, part):
        preds = scoring.predict_tensor(learner, spec, carry["hyps"], X)
        errs = scoring.error_matrix(preds, y, state.weights, use_pallas=use_pallas)
        return state, {**carry, "preds": preds, "errs": errs}

    def aggregate(state, carry, X, y, mask, part):
        hyps, preds, errs = carry["hyps"], carry["preds"], carry["errs"]
        eps = scoring.masked_error_sum(errs, part)  # responders' shards only
        c = scoring.masked_argmin(eps, part)  # responders' hypotheses only
        denom = scoring.participation_denom(state.weights, part)
        eps_c = eps[c] / denom  # exact identity under full participation
        alpha = _samme_alpha(eps_c, spec.n_classes)
        chosen = _take_slot(hyps, c)

        ens = state.ensemble
        ens = Ensemble(
            params=_set_slot(ens.params, ens.count, chosen),
            alpha=ens.alpha.at[ens.count].set(alpha),
            count=ens.count + 1,
        )
        mis = scoring.chosen_mis(preds, y, c)
        w = scoring.masked_update_weights(
            state.weights, mis, mask, part, alpha, use_pallas=use_pallas
        )
        metrics = {"epsilon": eps_c, "alpha": alpha, "chosen": c.astype(jnp.int32)}
        return BoostState(ens, w, state.key, state.fit_cache), {
            "metrics": metrics, "hyps": hyps
        }

    return [("fit", fit), ("score", score), ("aggregate", aggregate)]


def elastic_distboost_f_stages(
    learner, spec, *,
    use_pallas: bool = False, batched_fit: bool = True,
    block_s: int | None = None, block_d: int | None = None,
):
    """DistBoost.F with partial participation: the committee slot still
    holds all C member buffers, but only responders vote (the per-slot
    committee mask the caller records is ``part``)."""

    def fit(state, carry, X, y, mask, part):
        key, kfit = jax.random.split(state.key)
        committee = boosting._local_fits(
            learner, spec, state.weights, X, y, kfit, state.fit_cache,
            batched=batched_fit, use_pallas=use_pallas,
            block_s=block_s, block_d=block_d,
        )
        return BoostState(state.ensemble, state.weights, key, state.fit_cache), {
            "committee": committee
        }

    def score(state, carry, X, y, mask, part):
        committee = carry["committee"]

        def mis_one(Xi, yi):
            pred = scoring.masked_member_prediction(learner, spec, committee, part, Xi)
            return (pred != yi).astype(jnp.float32)

        mis = jax.vmap(mis_one)(X, y)
        return state, {**carry, "mis": mis}

    def aggregate(state, carry, X, y, mask, part):
        committee, mis = carry["committee"], carry["mis"]
        w = state.weights
        denom = scoring.participation_denom(w, part)
        masked_eps = jnp.sum(jnp.where(part[:, None] > 0, w * mis, 0.0)) / denom
        # lockstep ops on the full-participation branch (see scoring.py's
        # masked-reduction preamble for why the select alone isn't enough)
        eps = jnp.where(jnp.all(part > 0), jnp.sum(w * mis), masked_eps)
        alpha = _samme_alpha(eps, spec.n_classes)

        ens = state.ensemble
        ens = Ensemble(
            params=_set_slot(ens.params, ens.count, committee),
            alpha=ens.alpha.at[ens.count].set(alpha),
            count=ens.count + 1,
        )
        w = scoring.masked_update_weights(w, mis, mask, part, alpha, use_pallas=use_pallas)
        metrics = {"epsilon": eps, "alpha": alpha, "chosen": jnp.zeros((), jnp.int32)}
        return BoostState(ens, w, state.key, state.fit_cache), {"metrics": metrics}

    return [("fit", fit), ("score", score), ("aggregate", aggregate)]


def elastic_preweak_f_stages(learner, spec, hyp_space, *,
                             pred_cache: jax.Array | None = None,
                             use_pallas: bool = False):
    """PreWeak.F with partial participation: the C*T space was shipped at
    setup, so every hypothesis stays selectable — only the shard axis of
    the error reduction and the weight update are masked."""

    def score(state, carry, X, y, mask, part):
        preds = pred_cache if pred_cache is not None else boosting.preweak_f_predictions(
            learner, spec, hyp_space, X
        )
        errs = scoring.error_matrix(preds, y, state.weights, use_pallas=use_pallas)
        return state, {"preds": preds, "errs": errs}

    def aggregate(state, carry, X, y, mask, part):
        preds, errs = carry["preds"], carry["errs"]
        eps = scoring.masked_error_sum(errs, part)
        c = jnp.argmin(eps)  # whole space: every hypothesis was pre-shipped
        denom = scoring.participation_denom(state.weights, part)
        eps_c = eps[c] / denom
        alpha = _samme_alpha(eps_c, spec.n_classes)
        chosen = _take_slot(hyp_space, c)

        ens = state.ensemble
        ens = Ensemble(
            params=_set_slot(ens.params, ens.count, chosen),
            alpha=ens.alpha.at[ens.count].set(alpha),
            count=ens.count + 1,
        )
        mis = scoring.chosen_mis(preds, y, c)
        w = scoring.masked_update_weights(
            state.weights, mis, mask, part, alpha, use_pallas=use_pallas
        )
        metrics = {"epsilon": eps_c, "alpha": alpha, "chosen": c.astype(jnp.int32)}
        return BoostState(ens, w, state.key, state.fit_cache), {"metrics": metrics}

    return [("score", score), ("aggregate", aggregate)]


def elastic_bagging_stages(
    learner, spec, *,
    use_pallas: bool = False, batched_fit: bool = True,
    block_s: int | None = None, block_d: int | None = None,
):
    """Federated bagging with partial participation: the random member
    pick rotates over RESPONDERS (rank-select over the mask); with full
    participation the pick reduces to the lockstep draw bit-for-bit."""

    def fit(state, carry, X, y, mask, part):
        key, kfit, kpick = jax.random.split(state.key, 3)
        w = mask / jnp.maximum(jnp.sum(mask, axis=1, keepdims=True), 1.0)
        hyps = boosting._local_fits(
            learner, spec, w, X, y, kfit, state.fit_cache,
            batched=batched_fit, use_pallas=use_pallas,
            block_s=block_s, block_d=block_d,
        )
        return BoostState(state.ensemble, state.weights, key, state.fit_cache), {
            "hyps": hyps, "kpick": kpick
        }

    def aggregate(state, carry, X, y, mask, part):
        hyps, kpick = carry["hyps"], carry["kpick"]
        C = y.shape[0]
        c_raw = jax.random.randint(kpick, (), 0, C)
        resp = (part > 0).astype(jnp.int32)
        n_resp = jnp.maximum(jnp.sum(resp), 1)
        # map the raw draw onto the j-th responder; with all C responding
        # rank == arange(C) and c == c_raw exactly
        j = jnp.mod(c_raw, n_resp)
        rank = jnp.cumsum(resp) - 1
        c = jnp.argmax((resp > 0) & (rank == j)).astype(jnp.int32)
        ens = state.ensemble
        ens = Ensemble(
            params=_set_slot(ens.params, ens.count, _take_slot(hyps, c)),
            alpha=ens.alpha.at[ens.count].set(1.0),
            count=ens.count + 1,
        )
        metrics = {
            "epsilon": jnp.zeros(()), "alpha": jnp.ones(()),
            "chosen": c,
        }
        return BoostState(ens, state.weights, state.key, state.fit_cache), {
            "metrics": metrics, "hyps": hyps
        }

    return [("fit", fit), ("aggregate", aggregate)]


ELASTIC_STAGES = {
    "adaboost_f": elastic_adaboost_f_stages,
    "distboost_f": elastic_distboost_f_stages,
    "bagging": elastic_bagging_stages,
}

# algorithms whose round artifact is a single uploaded hypothesis — the
# only ones a straggler's late arrival can be merged for
_LATE_MERGE_ALGS = ("adaboost_f", "bagging")


def masked_ensemble_votes(learner, spec, ens: Ensemble, cmasks, X):
    """:func:`boosting.ensemble_votes` for elastic DistBoost.F ensembles:
    each committee slot votes through its own membership row of
    ``cmasks [T, C]``.  All-ones masks reproduce the lockstep bits."""
    T = ens.alpha.shape[0]

    def member_pred(t):
        return scoring.masked_member_prediction(
            learner, spec, _take_slot(ens.params, t), cmasks[t], X
        )

    preds = jax.vmap(member_pred)(jnp.arange(T))
    used = (jnp.arange(T) < ens.count).astype(jnp.float32) * ens.alpha
    onehot = jax.nn.one_hot(preds, spec.n_classes)
    return jnp.einsum("t,tnk->nk", used, onehot)


# ---------------------------------------------------------------------------
# Event-driven round closing (realtime mode)
# ---------------------------------------------------------------------------


class _ArrivalBoard:
    """Condition-variable arrival board — the ``DeadlineScheduler`` idiom
    applied to round closing: producers (per-collaborator timers, or
    real upload handlers in the distributed runtime) post ``(round,
    collaborator)`` arrivals; the round loop blocks in
    :meth:`close_round` until every expected collaborator posted or the
    deadline passes.  All shared state lives under ``self._cv``."""

    def __init__(self) -> None:
        self._cv = threading.Condition()
        self._posts: List[Tuple[int, int]] = []

    def post(self, round_idx: int, collaborator: int) -> None:
        with self._cv:
            self._posts.append((round_idx, collaborator))
            self._cv.notify_all()

    def close_round(
        self, round_idx: int, expected: Set[int], deadline_s: Optional[float],
        min_responders: int = 1,
    ) -> Tuple[Set[int], List[Tuple[int, int]], float, bool]:
        """Block until all of ``expected`` posted for ``round_idx`` or
        the deadline passes.  Returns ``(responders, late_posts, wait_s,
        deadline_hit)`` — ``late_posts`` are drained arrivals for EARLIER
        rounds (stragglers surfacing now); arrivals for this round that
        land after the deadline stay posted and surface at a later
        close.  The deadline never closes a round under
        ``min_responders`` arrivals: the wait stretches until the
        fastest ``min_responders`` land (every expected collaborator
        eventually posts — drops and deaths are excluded upstream)."""
        t0 = time.monotonic()
        cutoff = None if deadline_s is None else t0 + deadline_s
        floor = min(min_responders, len(expected))
        with self._cv:
            deadline_hit = False
            while True:
                have = {i for (rr, i) in self._posts if rr == round_idx}
                if expected <= have:
                    break
                timeout = None if cutoff is None else cutoff - time.monotonic()
                if timeout is not None and timeout <= 0:
                    if len(have & expected) >= floor:
                        deadline_hit = True
                        break
                    timeout = None  # under the responder floor: keep waiting
                self._cv.wait(timeout)
            responders = expected & {i for (rr, i) in self._posts if rr == round_idx}
            late = [(rr, i) for (rr, i) in self._posts if rr < round_idx]
            consumed = {(round_idx, i) for i in responders} | set(late)
            self._posts = [p for p in self._posts if p not in consumed]
        return responders, late, time.monotonic() - t0, deadline_hit


@dataclasses.dataclass(frozen=True)
class _LateItem:
    src_round: int
    collaborator: int
    lateness: int


# ---------------------------------------------------------------------------
# The elastic federation runtime
# ---------------------------------------------------------------------------


class ElasticFederation:
    """Round loop under a :class:`ParticipationPolicy` + :class:`FaultPlan`.

    Homogeneous fused-path federations only (the heterogeneous grouped
    rounds keep their lockstep loop for now); with ``policy.deadline_s
    is None`` and no faults, ``run`` is bit-for-bit ``Federation.run``.
    Normally constructed through ``Federation.run(policy=..., faults=...)``.
    """

    def __init__(
        self, plan: Plan, Xs, ys, masks, X_test, y_test, spec, key,
        *, policy: ParticipationPolicy, faults: Optional[FaultPlan] = None,
    ):
        plan.validate()
        policy.validate()
        if not isinstance(spec, LearnerSpec):
            raise NotImplementedError(
                "elastic rounds support homogeneous federations only; "
                "heterogeneous groups keep the lockstep loop"
            )
        if not plan.optimizations.fused_round or plan.algorithm == "fedavg":
            raise ValueError(
                "elastic rounds require the fused round path "
                "(optimizations.fused_round on, non-fedavg algorithm)"
            )
        self.plan = plan
        self.learner = get_learner(spec.name)
        self.spec = spec
        self.Xs, self.ys, self.masks = Xs, ys, masks
        self.X_test, self.y_test = X_test, y_test
        self.key = key
        self.policy = policy
        self.faults = faults or FaultPlan()
        self.n_collaborators = int(ys.shape[0])
        self.history: List[Dict[str, float]] = []
        self.late_log: List[Dict[str, float]] = []
        self.dropouts: Dict[str, int] = defaultdict(int)
        self.responders_log: List[int] = []
        self.comm_bytes = 0
        self.state: Optional[BoostState] = None
        self.published: List[Any] = []
        self._row_marker = (time.perf_counter(), 0, 0)

    # -- plumbing shared with Federation -----------------------------------
    def _account_comm(self, nbytes: int) -> None:
        self.comm_bytes += nbytes
        _M_COMM.inc(nbytes)

    def _history_extras(self, r: int) -> Dict[str, float]:
        now = time.perf_counter()
        t0, c0, r0 = self._row_marker
        k = max(r + 1 - r0, 1)
        self._row_marker = (now, self.comm_bytes, r + 1)
        dt = (now - t0) / k
        _M_ROUND_SECONDS.observe(dt)
        return {"round_seconds": dt, "comm_bytes": float(self.comm_bytes - c0)}

    def _slot_bytes(self, ens: Ensemble) -> int:
        return wire_size(ens.params) // max(ens.alpha.shape[0], 1)

    def _per_round_comm(self, h: int, n_resp: int) -> int:
        """The fused comm model of ``Federation._fused_comm_model`` with
        the collaborator count replaced by this round's responders."""
        alg = self.plan.algorithm
        if alg == "preweak_f":
            return 16 * n_resp
        if alg == "distboost_f":
            return h * (1 + n_resp) + 8 * n_resp
        if alg == "bagging":
            return n_resp * h
        return n_resp * h + n_resp * h * (n_resp - 1) + (h + 8) * n_resp

    # -- fault/membership resolution ---------------------------------------
    def _virtual_round(self, r: int, sched: FaultSchedule, active: np.ndarray):
        """Deterministic responder/late split for one round from the
        fault schedule's arrival times (no wall-clock waiting)."""
        deadline = self.policy.deadline_s
        act = np.nonzero(active[r])[0]
        delays = sched.delay[r]
        arrived = [i for i in act if not sched.drop[r, i]]
        if deadline is None:
            resp = list(arrived)
            late: List[Tuple[int, int]] = []
        else:
            resp = [i for i in arrived if delays[i] <= deadline]
            late = [(i, max(1, math.ceil(delays[i] / deadline) - 1))
                    for i in arrived if delays[i] > deadline]
            if len(resp) < self.policy.min_responders:
                # stretch the deadline to the fastest min_responders
                extra = sorted((i for i, _ in late), key=lambda i: delays[i])
                while len(resp) < self.policy.min_responders and extra:
                    i = extra.pop(0)
                    resp.append(i)
                    late = [(j, l) for j, l in late if j != i]
        resp_arr = np.zeros(self.n_collaborators, bool)
        resp_arr[resp] = True
        wait = 0.0
        if len(resp):
            wait = float(max(delays[i] for i in resp))
        deadline_hit = deadline is not None and len(resp) < len(act)
        if deadline_hit:
            wait = float(deadline)
        return resp_arr, late, wait, deadline_hit

    # -- main loop ---------------------------------------------------------
    def run(
        self,
        rounds: Optional[int] = None,
        eval_every: int = 1,
        *,
        publish_every: Optional[int] = None,
        publish_dir: Optional[str] = None,
        on_checkpoint=None,
    ) -> List[Dict[str, float]]:
        rounds = rounds or self.plan.aggregator.rounds
        pol, opt = self.policy, self.plan.optimizations
        alg = self.plan.algorithm
        C = self.n_collaborators
        up = opt.use_pallas
        sched = self.faults.schedule(rounds, C)
        active = pol.membership(rounds, C) & sched.alive & ~sched.offline

        # Late-merge slot budget: every (round, collaborator) whose delay
        # overshoots the deadline is a potential extra ensemble slot.
        # Exact in virtual mode, an upper bound in realtime mode — unused
        # slots stay zero-alpha and never vote.  Zero when no faults /
        # no deadline, so the ensemble shapes match lockstep exactly.
        late_budget = 0
        if pol.late_merge and pol.deadline_s is not None and alg in _LATE_MERGE_ALGS:
            late_budget = int(np.sum(active & (sched.delay > pol.deadline_s)))
        capacity = rounds + late_budget

        committee = C if alg == "distboost_f" else None
        state = boosting.init_boost_state(
            self.learner, self.spec, capacity, self.masks, self.key,
            committee_size=committee, X=self.Xs,
        )
        h = self._slot_bytes(state.ensemble)

        # -- jitted round / late-merge / eval programs (built once) --------
        if alg == "preweak_f":
            setup = jax.jit(
                lambda s, X, y, m: boosting.preweak_f_setup(
                    self.learner, self.spec, s, X, y, m, rounds
                )
            )
            with trace.span("preweak.setup", rounds=rounds):
                hyp_space, state = setup(state, self.Xs, self.ys, self.masks)
                cache = None
                if opt.cache_predictions:
                    cache = jax.jit(
                        lambda hs, X: boosting.preweak_f_predictions(
                            self.learner, self.spec, hs, X
                        )
                    )(hyp_space, self.Xs)
            stages = elastic_preweak_f_stages(
                self.learner, self.spec, hyp_space, pred_cache=cache, use_pallas=up
            )
            self._account_comm(wire_size(hyp_space) * C)
        else:
            stages = ELASTIC_STAGES[alg](
                self.learner, self.spec, use_pallas=up,
                batched_fit=opt.batched_fit,
                block_s=opt.tree_block_s, block_d=opt.tree_block_d,
            )
        round_fn = jax.jit(
            lambda s, X, y, m, p: run_elastic_stages(stages, s, X, y, m, p)
        )

        late_alpha_fn = None
        append_fn = None
        if alg in _LATE_MERGE_ALGS:
            def _late_alpha(hyps, idx, w, X, y, part):
                hyp = _take_slot(hyps, idx)
                preds = jax.vmap(lambda Xi: self.learner.predict(self.spec, hyp, Xi))(X)
                mis = (preds != y).astype(jnp.float32)
                eps = jnp.sum(jnp.where(part[:, None] > 0, w * mis, 0.0))
                mass = jnp.sum(jnp.where(part[:, None] > 0, w, 0.0))
                return _samme_alpha(eps / jnp.maximum(mass, 1e-30), self.spec.n_classes)

            def _append(s, hyps, idx, alpha):
                ens = s.ensemble
                ens = Ensemble(
                    params=_set_slot(ens.params, ens.count, _take_slot(hyps, idx)),
                    alpha=ens.alpha.at[ens.count].set(alpha),
                    count=ens.count + 1,
                )
                return BoostState(ens, s.weights, s.key, s.fit_cache)

            late_alpha_fn = jax.jit(_late_alpha)
            append_fn = jax.jit(_append)

        distboost = alg == "distboost_f"
        cmasks = jnp.ones((capacity, C), jnp.float32) if distboost else None
        if opt.cache_predictions:
            tally = scoring.init_tally(self.X_test.shape[0], self.spec.n_classes)
            if distboost:
                tally_fn = jax.jit(
                    lambda ens, cm, tl: scoring.tally_new_votes_masked(
                        self.learner, self.spec, ens, cm, tl, self.X_test
                    )
                )
            else:
                tally_fn = jax.jit(
                    lambda ens, cm, tl: scoring.tally_new_votes(
                        self.learner, self.spec, ens, tl, self.X_test
                    )
                )

            def evaluate(state, cmasks):
                nonlocal tally
                tally = tally_fn(state.ensemble, cmasks, tally)
                pred = scoring.tally_predict(tally)
                return f1_macro(self.y_test, pred, self.spec.n_classes)

        else:
            if distboost:
                predict = jax.jit(
                    lambda ens, cm, X: jnp.argmax(
                        masked_ensemble_votes(self.learner, self.spec, ens, cm, X),
                        axis=-1,
                    )
                )
            else:
                predict = jax.jit(
                    lambda ens, cm, X: boosting.strong_predict(
                        self.learner, self.spec, ens, X
                    )
                )

            def evaluate(state, cmasks):
                pred = predict(state.ensemble, cmasks, self.X_test)
                return f1_macro(self.y_test, pred, self.spec.n_classes)

        # -- the event-driven loop -----------------------------------------
        board = _ArrivalBoard() if pol.realtime else None
        timers: List[threading.Timer] = []
        pending: Dict[int, List[_LateItem]] = defaultdict(list)
        round_hyps: Dict[int, Any] = {}
        slot = 0  # host mirror of ensemble.count
        self._row_marker = (time.perf_counter(), self.comm_bytes, 0)
        try:
            for r in range(rounds):
                with trace.span("round", round=r, algorithm=alg, elastic=True):
                    # collaborators dying this round (counted once)
                    if r == 0:
                        died = np.nonzero(~sched.alive[0])[0]
                    else:
                        died = np.nonzero(sched.alive[r - 1] & ~sched.alive[r])[0]
                    for _ in died:
                        self.dropouts["dead"] += 1
                        _M_DROPOUT.labels(reason="dead").inc()

                    act_idx = np.nonzero(active[r])[0]
                    if pol.realtime:
                        expected = set()
                        for i in act_idx:
                            if sched.drop[r, i]:
                                continue
                            expected.add(int(i))  # np host scalar  # mafl: allow[host-sync]
                            d = float(sched.delay[r, i])  # np host scalar  # mafl: allow[host-sync]
                            if d <= 0:
                                board.post(r, int(i))  # mafl: allow[host-sync]
                            else:
                                t = threading.Timer(d, board.post, (r, int(i)))  # mafl: allow[host-sync]
                                t.daemon = True
                                t.start()
                                timers.append(t)
                        resp_set, late_posts, wait_s, deadline_hit = board.close_round(
                            r, expected, pol.deadline_s, pol.min_responders
                        )
                        resp_arr = np.zeros(C, bool)
                        resp_arr[sorted(resp_set)] = True
                        late_now = [
                            _LateItem(rr, i, r - rr)
                            for rr, i in late_posts
                        ]
                    else:
                        resp_arr, late_pairs, wait_s, deadline_hit = self._virtual_round(
                            r, sched, active
                        )
                        late_now = list(pending.pop(r, ()))
                        for i, lateness in late_pairs:
                            tgt = r + lateness
                            if (
                                pol.late_merge
                                and alg in _LATE_MERGE_ALGS
                                and lateness <= pol.max_staleness
                                and tgt < rounds
                            ):
                                pending[tgt].append(_LateItem(r, int(i), lateness))  # mafl: allow[host-sync]
                            else:
                                self.dropouts["stale"] += 1
                                _M_DROPOUT.labels(reason="stale").inc()

                    n_resp = int(resp_arr.sum())  # np host scalar  # mafl: allow[host-sync]
                    self.responders_log.append(n_resp)
                    # per-round dropout accounting over active members
                    for i in act_idx:
                        if resp_arr[i]:
                            continue
                        reason = "drop" if (not pol.realtime and sched.drop[r, i]) else "deadline"
                        self.dropouts[reason] += 1
                        _M_DROPOUT.labels(reason=reason).inc()

                    # late merges land first: they arrived while this
                    # round's window was open
                    part = jnp.asarray(resp_arr, jnp.float32)
                    n_late = 0
                    for item in sorted(
                        late_now, key=lambda it: (it.src_round, it.collaborator)
                    ):
                        if not (
                            pol.late_merge
                            and alg in _LATE_MERGE_ALGS
                            and item.lateness <= pol.max_staleness
                            and item.src_round in round_hyps
                        ):
                            self.dropouts["stale"] += 1
                            _M_DROPOUT.labels(reason="stale").inc()
                            continue
                        with trace.span(
                            "round.late_merge", round=r,
                            src_round=item.src_round,
                            collaborator=item.collaborator,
                            lateness=item.lateness,
                        ):
                            hyps_src = round_hyps[item.src_round]
                            idx = jnp.int32(item.collaborator)
                            if alg == "bagging":
                                base = jnp.float32(1.0)
                            else:
                                base = late_alpha_fn(
                                    hyps_src, idx, state.weights,
                                    self.Xs, self.ys, part,
                                )
                            disc = staleness_discount(
                                pol.staleness_gamma, item.lateness
                            )
                            alpha_late = base * jnp.float32(disc)
                            state = append_fn(state, hyps_src, idx, alpha_late)
                            self.late_log.append({
                                "src_round": item.src_round,
                                "merged_round": r,
                                "collaborator": item.collaborator,
                                "lateness": item.lateness,
                                "discount": disc,
                                "base_alpha": float(base),  # mafl: allow[host-sync]
                                "alpha": float(alpha_late),  # mafl: allow[host-sync]
                            })
                            if distboost:
                                pass  # unreachable: distboost never merges late
                            slot += 1
                            n_late += 1
                            _M_LATE_MERGES.inc()

                    if n_resp == 0:
                        # nobody answered at all: the round is lost, the
                        # state (incl. the PRNG key) is untouched
                        with trace.span(
                            "round.close", round=r, responders=0,
                            dropped=len(act_idx), late=n_late,
                            deadline_hit=deadline_hit, wait_s=wait_s,
                        ):
                            pass
                        _M_ROUNDS.inc()
                        continue

                    state, metrics, hyps = round_fn(
                        state, self.Xs, self.ys, self.masks, part
                    )
                    if distboost:
                        cmasks = cmasks.at[slot].set(part)
                    if hyps is not None and pol.late_merge and alg in _LATE_MERGE_ALGS:
                        round_hyps[r] = hyps
                        for rr in [k for k in round_hyps if k < r - pol.max_staleness]:
                            del round_hyps[rr]
                    slot += 1

                    with trace.span(
                        "round.close", round=r, responders=n_resp,
                        dropped=len(act_idx) - n_resp, late=n_late,
                        deadline_hit=deadline_hit, wait_s=wait_s,
                    ):
                        self._account_comm(self._per_round_comm(h, n_resp))
                    _M_ROUNDS.inc()

                    if (r + 1) % eval_every == 0 or r == rounds - 1:
                        with trace.span("round.eval", round=r):
                            f1 = evaluate(state, cmasks)
                        self.history.append(
                            {
                                "round": r,
                                "f1": float(f1),  # mafl: allow[host-sync]
                                **{k: float(v) for k, v in metrics.items()},  # mafl: allow[host-sync]
                                "responders": n_resp,
                                "late_merges": n_late,
                                "wait_s": wait_s,
                                **self._history_extras(r),
                            }
                        )
                    if publish_every and ((r + 1) % publish_every == 0 or r == rounds - 1):
                        with trace.span("round.publish", round=r):
                            self._publish_checkpoint(state, r, publish_dir, on_checkpoint)
        finally:
            for t in timers:
                t.cancel()
        # stragglers that never found a later round to merge into
        for items in pending.values():
            for _ in items:
                self.dropouts["stale"] += 1
                _M_DROPOUT.labels(reason="stale").inc()
        self.state = state
        self.cmasks = cmasks
        return self.history

    def _publish_checkpoint(self, state, round_idx, publish_dir, on_checkpoint):
        from repro.serve.artifact import publish_artifact

        committee = self.n_collaborators if self.plan.algorithm == "distboost_f" else None
        path = publish_artifact(
            publish_dir, self.spec, state.ensemble,
            version=round_idx + 1, committee_size=committee,
            extra={"round": round_idx + 1, "algorithm": self.plan.algorithm},
        )
        self.published.append(path)
        if on_checkpoint is not None:
            on_checkpoint(path, round_idx + 1)

    def summary(self) -> Dict[str, Any]:
        return {
            "algorithm": self.plan.algorithm,
            "collaborators": self.n_collaborators,
            "deadline_s": self.policy.deadline_s,
            "responders": list(self.responders_log),
            "dropouts": dict(self.dropouts),
            "late": list(self.late_log),
            "comm_bytes": self.comm_bytes,
            "history": list(self.history),
        }
