"""The MAFL federation runtime — Aggregator, Collaborators, Director/Envoy
(paper §4.3), driven by the Plan's task graph (core/protocol.py).

This is the OpenFL-faithful *simulation* layer: artifacts really travel
through serialized buffers and TensorDB entries, barriers really poll,
and every optimisation of paper §5.1 is a toggle — so the Fig.-3 ablation
is measurable.  The SPMD production path lives in fl/sharded.py.
"""
from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import boosting, hetero, protocol, scoring
from repro.core.aggregation import fedavg
from repro.core.hetero import HeterogeneousSpec
from repro.core.metrics import f1_macro
from repro.core.plan import Plan
from repro.core.serialization import deserialize, serialize, wire_format, wire_size
from repro.core.tensordb import TensorDB, TensorKey
from repro.learners.base import LearnerSpec, get_learner
from repro.obs import metrics as obs_metrics, trace

# Process-wide federation metric families (see docs/ARCHITECTURE.md,
# "Observability").  Declared at import time so any metrics dump covers
# them even before the first round runs.
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


@dataclasses.dataclass
class Collaborator:
    idx: int
    X: jax.Array  # [n, d]
    y: jax.Array  # [n]
    mask: jax.Array  # [n]
    weights: jax.Array  # [n] raw AdaBoost sample weights
    db: TensorDB
    params: Any = None  # current local model (FedAvg workflow)

    @property
    def origin(self) -> str:
        return f"collaborator_{self.idx}"


@dataclasses.dataclass
class Aggregator:
    db: TensorDB
    ensemble: List[Any] = dataclasses.field(default_factory=list)  # [(params, alpha)]
    global_params: Any = None  # FedAvg workflow


class Federation:
    """Instantiated by ``Director.start_experiment`` from a Plan (the
    long-lived Director/Envoy pair of OpenFL reduces to this factory in a
    single-process simulation)."""

    def __init__(self, plan: Plan, Xs, ys, masks, X_test, y_test, spec, key):
        """``spec`` is a ``LearnerSpec`` (homogeneous federation) or a
        ``core/hetero.HeterogeneousSpec`` (per-collaborator learner
        types).  A plan with a non-empty ``learners`` tuple upgrades a
        plain LearnerSpec by cycling the plan's learner types across
        collaborators (the LearnerSpec then only contributes the problem
        geometry)."""
        plan.validate()
        self.plan = plan
        if plan.learners and isinstance(spec, LearnerSpec):
            spec = HeterogeneousSpec.cycle(
                [lp.name for lp in plan.learners],
                Xs.shape[0],
                spec.n_features,
                spec.n_classes,
                hparams={lp.name: dict(lp.hparams) for lp in plan.learners},
            )
        self.hetero = isinstance(spec, HeterogeneousSpec)
        if self.hetero:
            if spec.n_collaborators != Xs.shape[0]:
                raise ValueError(
                    f"HeterogeneousSpec assigns {spec.n_collaborators} collaborators "
                    f"but the partition has {Xs.shape[0]}"
                )
            hetero.resolve(spec)  # fail fast on unknown registry keys
            self.learner = None  # per-group learners live in the spec
        else:
            self.learner = get_learner(spec.name)
        self.spec = spec
        self.key = key
        self.X_test, self.y_test = X_test, y_test
        opt = plan.optimizations
        retention = opt.tensordb_retention if opt.bounded_tensordb else None
        self.aggregator = Aggregator(db=TensorDB(retention))
        self.collaborators = [
            Collaborator(
                idx=i,
                X=Xs[i],
                y=ys[i],
                mask=masks[i],
                weights=masks[i] / jnp.maximum(jnp.sum(masks), 1.0),
                db=TensorDB(retention),
            )
            for i in range(Xs.shape[0])
        ]
        self.n_collaborators = len(self.collaborators)
        self.barrier = protocol.SynchBarrier(
            self.n_collaborators,
            sleep_s=plan.collaborator.sleep_s,
            structural=opt.fast_barrier,
        )
        self.end_round_sleep_s = 0.0 if opt.fast_barrier else max(plan.aggregator.sleep_s * 10, 0.1)
        self.comm_bytes = 0
        # (wall time, comm_bytes, round) at the previous history row —
        # feeds the rows' round_seconds / comm_bytes deltas
        self._row_marker = (time.perf_counter(), 0, 0)
        self.history: List[Dict[str, float]] = []
        self._round_scratch: Dict[str, Any] = {}
        self._fused_state: Optional[boosting.BoostState] = None
        self._fused_round_fn = None
        self._wire_fmt = None
        self._score_fn = None  # jitted predict-once shard scorer (lazy)
        self.published: List[Path] = []  # checkpoint artifacts, oldest first

    # -- communication accounting -----------------------------------------
    def _account_comm(self, nbytes: int) -> None:
        self.comm_bytes += nbytes
        _M_COMM.inc(nbytes)

    def send(self, tree: Any) -> List[bytes]:
        bufs = serialize(tree, packed=self.plan.optimizations.packed_serialization)
        self._account_comm(sum(len(b) for b in bufs))
        return bufs

    def recv(self, bufs: List[bytes], fmt) -> Any:
        return deserialize(bufs, fmt, packed=self.plan.optimizations.packed_serialization)

    def end_round_barrier(self, round_idx: int) -> None:
        if self.end_round_sleep_s:
            time.sleep(self.end_round_sleep_s)

    # -- main loop ---------------------------------------------------------
    def run(
        self,
        rounds: Optional[int] = None,
        eval_every: int = 1,
        *,
        publish_every: Optional[int] = None,
        publish_dir: Optional[str] = None,
        on_checkpoint: Optional[Callable[[Path, int], None]] = None,
        policy=None,
        faults=None,
    ) -> List[Dict[str, float]]:
        """Run the federation; optionally publish serving checkpoints.

        ``policy`` (an ``fl/elastic.ParticipationPolicy``) switches the
        round loop to the elastic runtime: straggler deadlines, partial
        participation, staleness-discounted late merges, and membership
        churn, optionally under a seeded ``faults``
        (``fl/elastic.FaultPlan``) injection schedule.  With no faults
        and ``deadline_s=None`` the elastic loop is bit-for-bit this
        method's lockstep fused path.

        ``publish_every=k`` emits a versioned serving artifact
        (``serve/artifact.publish_artifact``) into ``publish_dir`` every
        k rounds and after the final round — the continuous-training →
        continuous-serving handoff: capacity is fixed at ``rounds``, so
        successive checkpoints grow append-only and a ``ServeEngine`` /
        ``ShardVoteCache`` consumer folds only the appended members.
        ``on_checkpoint(path, round)`` fires after each publish (e.g. to
        hot-swap a live engine).  Publishing rides the fused path, which
        owns the fused ``BoostState``; the interpreted/FedAvg paths keep
        their list-of-pairs ensemble and do not publish.
        """
        rounds = rounds or self.plan.aggregator.rounds
        if policy is not None or faults is not None:
            from repro.fl.elastic import ElasticFederation, ParticipationPolicy

            if self.hetero:
                raise NotImplementedError(
                    "elastic rounds support homogeneous federations only; "
                    "heterogeneous groups keep the lockstep loop"
                )
            elastic = ElasticFederation(
                self.plan,
                jnp.stack([c.X for c in self.collaborators]),
                jnp.stack([c.y for c in self.collaborators]),
                jnp.stack([c.mask for c in self.collaborators]),
                self.X_test, self.y_test, self.spec, self.key,
                policy=policy or ParticipationPolicy(),
                faults=faults,
            )
            self.elastic = elastic
            history = elastic.run(
                rounds, eval_every,
                publish_every=publish_every, publish_dir=publish_dir,
                on_checkpoint=on_checkpoint,
            )
            # mirror the fused path's externally visible state
            self.history = elastic.history
            self._fused_state = elastic.state
            self.comm_bytes += elastic.comm_bytes
            self.published.extend(elastic.published)
            return history
        if self.hetero and not (
            self.plan.optimizations.fused_round and self.plan.algorithm != "fedavg"
        ):
            raise ValueError(
                "heterogeneous federations require the fused round path "
                "(optimizations.fused_round on, non-fedavg algorithm): the "
                "interpreted simulation and fedavg assume one hypothesis pytree"
            )
        if publish_every is not None:
            if publish_every <= 0:
                raise ValueError(f"publish_every must be positive, got {publish_every}")
            if publish_dir is None:
                raise ValueError("publish_every requires a publish_dir")
            if not (self.plan.optimizations.fused_round and self.plan.algorithm != "fedavg"):
                raise ValueError(
                    "checkpoint publishing requires the fused round path "
                    "(optimizations.fused_round on, non-fedavg algorithm)"
                )
        if self.plan.optimizations.fused_round and self.plan.algorithm != "fedavg":
            run = self._run_fused_hetero if self.hetero else self._run_fused
            return run(
                rounds, eval_every,
                publish_every=publish_every, publish_dir=publish_dir,
                on_checkpoint=on_checkpoint,
            )
        self._eval_every = eval_every
        self._row_marker = (time.perf_counter(), self.comm_bytes, 0)
        for r in range(rounds):
            with trace.span("round", round=r, algorithm=self.plan.algorithm):
                protocol.run_round(self, r)
            _M_ROUNDS.inc()
        return self.history

    def _history_extras(self, r: int) -> Dict[str, float]:
        """round_seconds / comm_bytes deltas since the previous history
        row (per-round averages when rows are sparser than rounds — no
        extra device syncs are added to measure them)."""
        now = time.perf_counter()
        t0, c0, r0 = self._row_marker
        k = max(r + 1 - r0, 1)
        self._row_marker = (now, self.comm_bytes, r + 1)
        dt = (now - t0) / k
        _M_ROUND_SECONDS.observe(dt)
        return {"round_seconds": dt, "comm_bytes": float(self.comm_bytes - c0)}

    def _publish_checkpoint(self, state: boosting.BoostState, round_idx: int,
                            publish_dir: str, on_checkpoint) -> None:
        """One rolling-artifact checkpoint (version = 1-based round)."""
        from repro.serve.artifact import publish_artifact  # serve is optional at train time

        committee = (
            self.n_collaborators if self.plan.algorithm == "distboost_f" else None
        )
        path = publish_artifact(
            publish_dir, self.spec, state.ensemble,
            version=round_idx + 1, committee_size=committee,
            extra={"round": round_idx + 1, "algorithm": self.plan.algorithm},
        )
        self.published.append(path)
        if on_checkpoint is not None:
            on_checkpoint(path, round_idx + 1)

    # -- fused fast path: the whole round as one jitted program ------------
    def _fused_comm_model(self, state, *, setup_tree=None) -> tuple:
        """(setup_bytes, per_round_bytes) for the fused path.

        The fused round never serializes, so the wire traffic is modelled
        analytically from artifact shapes (``wire_size`` is shape-only —
        no device sync), mirroring the interpreted path's accounting:
        per round every collaborator uploads its local hypothesis, the
        aggregator broadcasts the hypothesis space for validation (C-1
        extra wire copies, as in ``weak_learners_validate``) and then the
        (chosen hypothesis, alpha) pair (``adaboost_update``).  PreWeak.F
        ships the whole C*T space once at setup and only (alpha, index)
        per round; bagging skips both broadcasts.
        """
        C = self.n_collaborators
        ens = state.ensemble
        # homogeneous Ensemble is itself a NamedTuple — only a plain tuple
        # is the heterogeneous per-group collection
        parts = ens if not isinstance(ens, boosting.Ensemble) else (ens,)
        # one ensemble slot's bytes: the slot buffers' leading dim is the
        # capacity, so a slot is total/capacity
        h = sum(wire_size(e.params) // max(e.alpha.shape[0], 1) for e in parts)
        alg = self.plan.algorithm
        if alg == "preweak_f":
            setup = wire_size(setup_tree) * C if setup_tree is not None else 0
            return setup, 16 * C  # (alpha, chosen index) broadcast
        if alg == "distboost_f":
            # the slot IS the whole committee: its upload is the C local
            # fits; validation re-broadcasts it to every collaborator
            return 0, h * (1 + C) + 8 * C
        if alg == "bagging":
            return 0, C * h  # uploads only — no scoring, no weight update
        return 0, C * h + C * h * (C - 1) + (h + 8) * C  # adaboost_f

    def _fused_loop(
        self, rounds: int, eval_every: int, state, Xs, ys, masks,
        round_fn, evaluate, per_round_comm: int,
        publish_every, publish_dir, on_checkpoint,
    ) -> List[Dict[str, float]]:
        """The round loop shared by both fused paths.

        Traced or not, every round is one call of the jitted ``round_fn``.
        The host spans are ``round``, ``round.eval`` and
        ``round.publish``; fit / score / aggregate are named scopes inside
        the program (``boosting.run_stages``), seen in a profiler trace.
        """
        self._row_marker = (time.perf_counter(), self.comm_bytes, 0)
        for r in range(rounds):
            with trace.span("round", round=r, algorithm=self.plan.algorithm):
                state, metrics = round_fn(state, Xs, ys, masks)
                self._account_comm(per_round_comm)
                _M_ROUNDS.inc()
                if (r + 1) % eval_every == 0 or r == rounds - 1:
                    with trace.span("round.eval", round=r):
                        f1 = evaluate(state)
                    self.history.append(
                        {
                            "round": r,
                            # once per eval_every, right after block_until_ready:
                            # the sync is the point here, not a hazard
                            "f1": float(f1),  # mafl: allow[host-sync]
                            **{k: float(v) for k, v in metrics.items()},  # mafl: allow[host-sync]
                            **self._history_extras(r),
                        }
                    )
                if publish_every and ((r + 1) % publish_every == 0 or r == rounds - 1):
                    # the fused state owns the slot-buffer ensemble: each
                    # checkpoint is the same capacity with a larger count, so
                    # the artifact stream is append-only by construction
                    with trace.span("round.publish", round=r):
                        self._publish_checkpoint(state, r, publish_dir, on_checkpoint)
        self._fused_state = state
        return self.history

    def _run_fused(
        self, rounds: int, eval_every: int,
        *, publish_every: Optional[int] = None, publish_dir: Optional[str] = None,
        on_checkpoint=None,
    ) -> List[Dict[str, float]]:
        Xs = jnp.stack([c.X for c in self.collaborators])
        ys = jnp.stack([c.y for c in self.collaborators])
        masks = jnp.stack([c.mask for c in self.collaborators])
        opt = self.plan.optimizations
        up = opt.use_pallas
        committee = self.n_collaborators if self.plan.algorithm == "distboost_f" else None
        state = boosting.init_boost_state(
            self.learner, self.spec, rounds, masks, self.key,
            committee_size=committee, X=Xs,  # X-static fit cache (e.g. tree bin edges)
        )
        if self.plan.algorithm == "preweak_f":
            setup = jax.jit(
                lambda s, X, y, m: boosting.preweak_f_setup(
                    self.learner, self.spec, s, X, y, m, rounds
                )
            )
            with trace.span("preweak.setup", rounds=rounds):
                hyp_space, state = setup(state, Xs, ys, masks)
                # The C*T hypothesis space is static across rounds: predict
                # it once at setup and every round becomes a pure reduction.
                cache = None
                if opt.cache_predictions:
                    cache = jax.jit(
                        lambda hs, X: boosting.preweak_f_predictions(
                            self.learner, self.spec, hs, X
                        )
                    )(hyp_space, Xs)
            round_fn = jax.jit(
                lambda s, X, y, m: boosting.preweak_f_round(
                    self.learner, self.spec, s, hyp_space, X, y, m,
                    pred_cache=cache, use_pallas=up,
                )
            )
            setup_bytes, per_round = self._fused_comm_model(state, setup_tree=hyp_space)
            self._account_comm(setup_bytes)
        else:
            base = boosting.ROUND_FNS[self.plan.algorithm]
            round_fn = jax.jit(
                lambda s, X, y, m: base(
                    self.learner, self.spec, s, X, y, m, use_pallas=up,
                    batched_fit=opt.batched_fit,
                    block_s=opt.tree_block_s, block_d=opt.tree_block_d,
                )
            )
            _, per_round = self._fused_comm_model(state)
        committee_pred = self.plan.algorithm == "distboost_f"
        if opt.cache_predictions:
            # incremental eval: running vote tally; each eval adds only the
            # members appended since the previous one
            tally = scoring.init_tally(self.X_test.shape[0], self.spec.n_classes)
            tally_fn = jax.jit(jax.named_scope("eval")(
                lambda ens, tl: scoring.tally_new_votes(
                    self.learner, self.spec, ens, tl, self.X_test,
                    committee=committee_pred,
                )
            ))

            def evaluate(state):
                nonlocal tally
                tally = tally_fn(state.ensemble, tally)
                pred = scoring.tally_predict(tally)
                return f1_macro(self.y_test, pred, self.spec.n_classes)

        else:
            predict = jax.jit(jax.named_scope("eval")(
                lambda ens, X: boosting.strong_predict(
                    self.learner, self.spec, ens, X, committee=committee_pred
                )
            ))

            def evaluate(state):
                pred = predict(state.ensemble, self.X_test)
                return f1_macro(self.y_test, pred, self.spec.n_classes)

        return self._fused_loop(
            rounds, eval_every, state, Xs, ys, masks, round_fn,
            evaluate, per_round, publish_every, publish_dir, on_checkpoint,
        )

    # -- fused fast path, heterogeneous: per-collaborator learner types ----
    def _run_fused_hetero(
        self, rounds: int, eval_every: int,
        *, publish_every: Optional[int] = None, publish_dir: Optional[str] = None,
        on_checkpoint=None,
    ) -> List[Dict[str, float]]:
        """The heterogeneous mirror of ``_run_fused``: same round loop,
        same §5.1 toggles, but the state/round/eval machinery comes from
        ``core/hetero.py`` (grouped fits, cross-group voting, per-group
        vote tallies).  With a single learner group every step reduces
        to the homogeneous operations bit-for-bit."""
        hspec: HeterogeneousSpec = self.spec
        Xs = jnp.stack([c.X for c in self.collaborators])
        ys = jnp.stack([c.y for c in self.collaborators])
        masks = jnp.stack([c.mask for c in self.collaborators])
        opt = self.plan.optimizations
        up = opt.use_pallas
        committee = self.plan.algorithm == "distboost_f"
        state = hetero.init_hetero_boost_state(
            hspec, rounds, masks, self.key, committee=committee, X=Xs,
        )
        if self.plan.algorithm == "preweak_f":
            setup = jax.jit(
                lambda s, X, y, m: hetero.hetero_preweak_f_setup(
                    hspec, s, X, y, m, rounds
                )
            )
            with trace.span("preweak.setup", rounds=rounds):
                spaces, state = setup(state, Xs, ys, masks)
                cache = None
                if opt.cache_predictions:
                    cache = jax.jit(
                        lambda sp, X: hetero.hetero_preweak_f_predictions(hspec, sp, X)
                    )(spaces, Xs)
            round_fn = jax.jit(
                lambda s, X, y, m: hetero.hetero_preweak_f_round(
                    hspec, s, spaces, X, y, m, pred_cache=cache, use_pallas=up,
                )
            )
            setup_bytes, per_round = self._fused_comm_model(state, setup_tree=spaces)
            self._account_comm(setup_bytes)
        else:
            base = hetero.HETERO_ROUND_FNS[self.plan.algorithm]
            round_fn = jax.jit(
                lambda s, X, y, m: base(
                    hspec, s, X, y, m, use_pallas=up,
                    batched_fit=opt.batched_fit,
                    block_s=opt.tree_block_s, block_d=opt.tree_block_d,
                )
            )
            _, per_round = self._fused_comm_model(state)
        if opt.cache_predictions:
            tallies = hetero.init_hetero_tally(
                hspec, self.X_test.shape[0], committee=committee
            )
            tally_fn = jax.jit(jax.named_scope("eval")(
                lambda ens, tl: hetero.hetero_tally_new_votes(
                    hspec, ens, tl, self.X_test, committee=committee,
                )
            ))

            def evaluate(state):
                nonlocal tallies
                tallies = tally_fn(state.ensemble, tallies)
                pred = hetero.hetero_tally_predict(tallies)
                return f1_macro(self.y_test, pred, hspec.n_classes)

        else:
            predict = jax.jit(jax.named_scope("eval")(
                lambda ens, X: hetero.hetero_strong_predict(
                    hspec, ens, X, committee=committee
                )
            ))

            def evaluate(state):
                pred = predict(state.ensemble, self.X_test)
                return f1_macro(self.y_test, pred, hspec.n_classes)

        return self._fused_loop(
            rounds, eval_every, state, Xs, ys, masks, round_fn,
            evaluate, per_round, publish_every, publish_dir, on_checkpoint,
        )

    # -- ensemble as used by the interpreted path --------------------------
    def strong_predict_host(self, X) -> jax.Array:
        if not self.aggregator.ensemble:
            return jnp.zeros(X.shape[0], jnp.int32)
        votes = jnp.zeros((X.shape[0], self.spec.n_classes))
        for params, alpha in self.aggregator.ensemble:
            pred = self.learner.predict(self.spec, params, X)
            votes = votes + alpha * jax.nn.one_hot(pred, self.spec.n_classes)
        return jnp.argmax(votes, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Task executors (interpreted mode) — the paper's §4.1 task vocabulary
# ---------------------------------------------------------------------------


@protocol.task_executor("train")
def _train(fed: Federation, r: int, args: Dict[str, Any]) -> None:
    if fed.plan.algorithm == "fedavg":
        _fedavg_train(fed, r)
        return
    for c in fed.collaborators:
        # local fit on AdaBoost weights (scaled locally so scale-sensitive
        # learners keep their regularisation semantics)
        wsum = jnp.maximum(jnp.sum(c.weights), 1e-30)
        w_fit = c.weights / wsum * jnp.maximum(jnp.sum(c.mask), 1.0)
        fed.key, kfit = jax.random.split(fed.key)
        params = fed.learner.fit(fed.spec, None, c.X, c.y, w_fit, kfit)
        if fed._wire_fmt is None:
            fed._wire_fmt = wire_format(params)
        bufs = fed.send(params)  # collaborator -> aggregator
        fed.aggregator.db.put(TensorKey("weak_hypothesis", c.origin, r), bufs)


@protocol.task_executor("weak_learners_validate")
def _weak_learners_validate(fed: Federation, r: int, args: Dict[str, Any]) -> None:
    # aggregator broadcasts the whole hypothesis space to every collaborator
    entries = fed.aggregator.db.query(name="weak_hypothesis", round=r)
    entries.sort(key=lambda kv: kv[0].origin)
    hyps = [fed.recv(bufs, fed._wire_fmt) for _, bufs in entries]
    fed._account_comm(
        sum(sum(len(b) for b in bufs) for _, bufs in entries)
        * (fed.n_collaborators - 1)
    )  # n-1 extra copies on the wire
    # predict-once batched scoring: stack the hypothesis space and score
    # each collaborator's shard with ONE jitted call (a kernel-backed
    # reduction over the materialised [H, n] predictions) instead of the
    # C x H Python double loop with a per-element float() device sync.
    hyp_stack = jax.tree.map(lambda *ls: jnp.stack(ls), *hyps)
    if fed._score_fn is None:
        up = fed.plan.optimizations.use_pallas

        def _score(hs, X, y, w):
            preds = scoring.predict_matrix(fed.learner, fed.spec, hs, X)
            return preds, scoring.shard_errors(preds, y, w, use_pallas=up), jnp.sum(w)

        fed._score_fn = jax.jit(_score)
    err_rows, norm_vals, pred_rows = [], [], []
    for i, c in enumerate(fed.collaborators):
        preds_i, errs_i, norm_i = fed._score_fn(hyp_stack, c.X, c.y, c.weights * c.mask)
        pred_rows.append(preds_i)  # reused by adaboost_update — no re-predict
        err_rows.append(errs_i)
        norm_vals.append(norm_i)
        c.db.put(TensorKey("misprediction", c.origin, r), None)
    # one stacked transfer for the whole round instead of a device sync per
    # collaborator; the f32 -> f64 casts are exact, so downstream host math
    # matches the old per-element float() accumulation bit for bit
    errs = np.asarray(jnp.stack(err_rows), dtype=np.float64)
    norms = np.asarray(jnp.stack(norm_vals), dtype=np.float64)
    fed._round_scratch = {"errs": errs, "norms": norms, "hyps": hyps, "preds": pred_rows}
    fed.aggregator.db.put(TensorKey("error_matrix", "aggregator", r), errs)


@protocol.task_executor("adaboost_update")
def _adaboost_update(fed: Federation, r: int, args: Dict[str, Any]) -> None:
    errs = fed._round_scratch["errs"]
    norms = fed._round_scratch["norms"]
    hyps = fed._round_scratch["hyps"]
    eps = errs.sum(axis=0) / max(norms.sum(), 1e-30)
    c_idx = int(np.argmin(eps))
    e = float(np.clip(eps[c_idx], 1e-10, 1 - 1e-10))
    alpha = float(np.clip(np.log((1 - e) / e) + np.log(fed.spec.n_classes - 1.0), -10, 10))
    chosen = hyps[c_idx]
    fed.aggregator.ensemble.append((chosen, alpha))
    fed.aggregator.db.put(TensorKey("adaboost_coeff", "aggregator", r), alpha)
    # broadcast (chosen hypothesis, alpha); collaborators update weights
    fed._account_comm((wire_size(chosen) + 8) * fed.n_collaborators)
    up = fed.plan.optimizations.use_pallas
    pred_rows = fed._round_scratch.get("preds")
    wsums = []
    for i, c in enumerate(fed.collaborators):
        # chosen-hypothesis mispredictions: a row slice of the predictions
        # already materialised by weak_learners_validate — no re-predict
        mis = (pred_rows[i][c_idx] != c.y).astype(jnp.float32)
        c.weights = scoring.update_weights(
            c.weights, mis, c.mask, jnp.float32(alpha),
            use_pallas=up, renormalize=False,  # global renorm via norm exchange below
        )
        wsums.append(jnp.sum(c.weights))
    # single stacked transfer; Python's left-to-right sum over the exact
    # f64 casts reproduces the old per-collaborator float() accumulation
    total = sum(np.asarray(jnp.stack(wsums), dtype=np.float64).tolist())
    for c in fed.collaborators:  # global renormalisation via norm exchange
        c.weights = c.weights / max(total, 1e-30)


@protocol.task_executor("adaboost_validate")
def _adaboost_validate(fed: Federation, r: int, args: Dict[str, Any]) -> None:
    if (r + 1) % getattr(fed, "_eval_every", 1) and r != fed.plan.aggregator.rounds - 1:
        return
    pred = fed.strong_predict_host(fed.X_test)
    f1 = float(f1_macro(fed.y_test, pred, fed.spec.n_classes))
    last = fed.aggregator.ensemble[-1] if fed.aggregator.ensemble else (None, 0.0)
    fed.history.append(
        {"round": r, "f1": f1, "alpha": last[1], **fed._history_extras(r)}
    )
    fed.aggregator.db.put(TensorKey("metric/f1", "aggregator", r), f1)


# -- OpenFL's original DNN workflow (FedAvg over warm-started learners) ----


def _fedavg_train(fed: Federation, r: int) -> None:
    if fed.learner.warm_fit is None:
        raise ValueError(f"learner {fed.spec.name!r} has no warm_fit; FedAvg needs one")
    if fed.aggregator.global_params is None:
        fed.key, k0 = jax.random.split(fed.key)
        fed.aggregator.global_params = fed.learner.init(fed.spec, k0)
    locals_, sizes = [], []
    for c in fed.collaborators:
        fed.key, kt = jax.random.split(fed.key)
        fed._account_comm(wire_size(fed.aggregator.global_params))  # broadcast
        p = fed.learner.warm_fit(fed.spec, fed.aggregator.global_params, c.X, c.y, c.mask, kt)
        c.params = p
        fed._account_comm(wire_size(p))  # upload
        locals_.append(p)
        sizes.append(jnp.sum(c.mask).astype(jnp.float32))  # stays on device
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *locals_)
    fed.aggregator.global_params = fedavg(stacked, jnp.stack(sizes))


@protocol.task_executor("aggregated_model_validation")
def _agg_model_validation(fed: Federation, r: int, args) -> None:
    if fed.aggregator.global_params is None:
        return
    pred = fed.learner.predict(fed.spec, fed.aggregator.global_params, fed.X_test)
    fed.history.append(
        {
            "round": r,
            "f1": float(f1_macro(fed.y_test, pred, fed.spec.n_classes)),
            "alpha": 0.0,
            **fed._history_extras(r),
        }
    )


@protocol.task_executor("locally_tuned_model_validation")
def _local_model_validation(fed: Federation, r: int, args) -> None:
    for c in fed.collaborators:
        if c.params is None:
            continue
        pred = fed.learner.predict(fed.spec, c.params, c.X)
        c.db.put(
            TensorKey("metric/local_f1", c.origin, r),
            # validation-only task: one metric per collaborator is the output
            float(f1_macro(c.y, pred, fed.spec.n_classes)),  # mafl: allow[host-sync]
        )
