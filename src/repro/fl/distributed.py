"""Process-per-collaborator MAFL runtime — the paper's OpenFL deployment
topology as real OS processes over JAX collectives.

Every other execution path in this repo (fused jit, interpreted
simulation, SPMD ``fl/sharded.py``) runs in ONE process, so its comm
counters are modelled or fake-device quantities.  Here each collaborator
IS a process (``jax.distributed.initialize`` + ``jax.process_index()``),
the per-round hypothesis broadcast is an actual ``all_gather`` between
processes (packed into one wire buffer per round via the
``fl/sharded.py`` packing), and ``mafl_federation_comm_bytes_total``
counts the bytes those collectives really move.

Topology (paper §4.3, OpenFL coordinator/collaborator):

  process i (i = 1..C-1)   collaborator i — owns shard i, fits locally,
                           scores the broadcast hypothesis space on its
                           shard only
  process 0                collaborator 0 AND the coordinator: evaluates
                           on the test split, owns the history rows, and
                           publishes serving checkpoints

Aggregation (paper step 3/4) is *replicated*: every process runs the
identical argmin/alpha/weight-update on the identical gathered error
quantities, so the full ``BoostState`` stays replicated without a
per-round state broadcast — exactly the SPMD trick of ``fl/sharded.py``,
but across processes.

Bit-exactness contract: a C-process run is bit-for-bit identical to the
single-process fused federation (history, weights, final ensemble) for
batch-invariant learners (trees, gaussian_nb — NOT ridge, whose batched
linear solve differs in ulps from C single solves).  Three properties
make this hold, all regression-tested in tests/test_distributed.py:

  * the fused fit paths are batch-invariant (PR-3: ``fit_batched`` ==
    ``vmap(fit_cached)`` == C single fits, bit-for-bit);
  * every scoring reduction is row-independent (``weighted_errors_ref``
    reduces with a last-axis sum, not a batch-size-tiled matvec);
  * ``boosting.run_stages`` seals stage boundaries with an
    ``optimization_barrier``, so the fused jit cannot fuse reductions
    across the boundary that is a real network collective here.

Collective schedule per round (H = hypothesis-space size):

  algorithm     collectives                              payload
  adaboost_f    hyps gather, errs gather, mis gather     [C,·] [C,H] [C,n]
  distboost_f   hyps gather, mis gather                  [C,·] [C,n]
  bagging       hyps gather                              [C,·]
  preweak_f     (setup: space gather [C,T,·])            then per round
                errs gather, mis gather                  [C,H] [C,n]
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import boosting, scoring
from repro.core.hetero import HeterogeneousSpec
from repro.core.metrics import f1_macro
from repro.core.plan import Plan
from repro.fl.sharded import _pack_leaves, _unpack_leaves
from repro.learners.base import LearnerSpec, get_learner
from repro.obs import metrics as obs_metrics, trace

# Same process-wide families as fl/federation.py (the registry returns
# the existing metric on re-registration) — the distributed path is the
# one place where comm bytes are measured collective payloads.
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

_INITIALIZED = False


def pin_cpu() -> None:
    """Pin this process to the CPU backend.

    The multi-process runtimes (gloo collectives here, the TCP star of
    ``fl/elastic_dist.py``) are CPU transports with one process per
    collaborator.  An accelerator belongs to one process at a time, so
    none of these processes may reach for it.  Must run before the
    process's first JAX computation."""
    jax.config.update("jax_platforms", "cpu")


def initialize(coordinator_address: str, num_processes: int, process_id: int) -> None:
    """Join the federation's process group (idempotent).

    Must run before any other JAX call in the process: it pins the
    process to the CPU (:func:`pin_cpu`), selects the gloo CPU collective
    backend and registers with the coordinator service (process 0 hosts
    it at ``coordinator_address``).  With ``num_processes=1`` this still
    goes through ``jax.distributed`` so a 1-process run exercises the
    identical code path as the N-process bench points.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    pin_cpu()
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _INITIALIZED = True


def is_main() -> bool:
    """True on the coordinator (process 0) — the multi-host launch idiom:
    exactly one process prints, evaluates, and publishes."""
    return jax.process_index() == 0


class DistributedFederation:
    """The multi-process mirror of ``fl/federation.Federation``'s fused
    path: same Plan, same round semantics, one process per collaborator.

    Every process constructs this with the SAME full partition
    (deterministic from the shared seed) so state init — including the
    vmapped fit cache — is bit-identical to the fused path; each process
    then keeps only its own shard's data for the round loop.
    """

    def __init__(
        self, plan: Plan, Xs, ys, masks, X_test, y_test, spec, key,
        *, packed_broadcast: bool = True,
    ):
        plan.validate()
        if plan.learners or isinstance(spec, HeterogeneousSpec):
            raise NotImplementedError(
                "distributed runtime is homogeneous-only: a process-per-"
                "collaborator round gathers ONE hypothesis pytree structure"
            )
        if plan.algorithm == "fedavg":
            raise NotImplementedError("distributed runtime covers the MAFL "
                                      "boosting algorithms, not fedavg")
        C = Xs.shape[0]
        if jax.process_count() != C:
            raise ValueError(
                f"process-per-collaborator: {C} collaborators need "
                f"{C} processes, have {jax.process_count()}"
            )
        self.plan = plan
        self.spec = spec
        self.learner = get_learner(spec.name)
        self.C = C
        self.pidx = int(jax.process_index())
        self.key = key
        self.masks = masks  # full [C, n] — the replicated weight update needs it
        self.Xi, self.yi, self.maski = Xs[self.pidx], ys[self.pidx], masks[self.pidx]
        self._Xs = Xs  # only for bit-identical state init; dropped in run()
        self.X_test, self.y_test = X_test, y_test
        self.packed_broadcast = packed_broadcast
        self.comm_bytes = 0
        self.collective_calls = 0
        self.comm_breakdown: Dict[str, int] = {}
        self._row_marker = (time.perf_counter(), 0, 0)
        self.history: List[Dict[str, float]] = []
        self.published: List[Any] = []
        self.state: Optional[boosting.BoostState] = None

    # -- communication ------------------------------------------------------

    def _gather(self, x, *, span_name: str, r: int, label: str):
        """ONE all-gather across the process group; returns the [C, ...]
        gathered space (host arrays, process-index ordered).  Accounts the
        gathered payload — the bytes every process materialises off the
        collective — into the comm counter and the span."""
        from jax.experimental import multihost_utils

        with trace.span(span_name, round=r, payload=label,
                        collective="all_gather") as sp:
            out = multihost_utils.process_allgather(x, tiled=False)
            nbytes = int(sum(l.nbytes for l in jax.tree.leaves(out)))
            sp.set(bytes=nbytes)
        self.comm_bytes += nbytes
        self.collective_calls += 1
        self.comm_breakdown[label] = self.comm_breakdown.get(label, 0) + nbytes
        _M_COMM.inc(nbytes)
        return out

    def _gather_hyps(self, h_local, r: int, *, label: str = "hypotheses"):
        """The per-round hypothesis broadcast (paper step 2 -> 3 handoff).

        ``packed_broadcast`` ON (the §5.1 buffer-packing analogue, same
        packing as ``fl/sharded.py``): the local hypothesis pytree is
        flattened into ONE i32 wire buffer, so the broadcast is a single
        collective per round.  OFF: one collective per leaf — the
        pre-optimisation OpenFL behaviour, kept as the ``BENCH_distributed``
        ablation arm.  Both are lossless (f32 leaves travel bitcast), so
        the ablation changes wire schedule, never results.
        """
        if self.packed_broadcast:
            buf, fmt = _pack_leaves(h_local)
            g = self._gather(buf, span_name="round.broadcast", r=r, label=label)
            return _unpack_leaves(jnp.asarray(g), fmt, lead=(self.C,))
        leaves, treedef = jax.tree.flatten(h_local)
        gathered = [
            jnp.asarray(self._gather(l, span_name="round.broadcast", r=r, label=label))
            for l in leaves
        ]
        return jax.tree.unflatten(treedef, gathered)

    def _history_extras(self, r: int) -> Dict[str, float]:
        now = time.perf_counter()
        t0, c0, r0 = self._row_marker
        k = max(r + 1 - r0, 1)
        self._row_marker = (now, self.comm_bytes, r + 1)
        dt = (now - t0) / k
        _M_ROUND_SECONDS.observe(dt)
        return {"round_seconds": dt, "comm_bytes": float(self.comm_bytes - c0)}

    def _publish_checkpoint(self, state, round_idx: int, publish_dir, on_checkpoint):
        from repro.serve.artifact import publish_artifact

        committee = self.C if self.plan.algorithm == "distboost_f" else None
        path = publish_artifact(
            publish_dir, self.spec, state.ensemble,
            version=round_idx + 1, committee_size=committee,
            extra={"round": round_idx + 1, "algorithm": self.plan.algorithm},
        )
        self.published.append(path)
        if on_checkpoint is not None:
            on_checkpoint(path, round_idx + 1)

    # -- main loop ----------------------------------------------------------

    def run(
        self,
        rounds: Optional[int] = None,
        eval_every: int = 1,
        *,
        publish_every: Optional[int] = None,
        publish_dir: Optional[str] = None,
        on_checkpoint: Optional[Callable] = None,
    ) -> List[Dict[str, float]]:
        """Run the federation; returns this process's history (``f1`` is
        present only on process 0, which owns evaluation)."""
        rounds = rounds or self.plan.aggregator.rounds
        if publish_every is not None:
            if publish_every <= 0:
                raise ValueError(f"publish_every must be positive, got {publish_every}")
            if publish_dir is None:
                raise ValueError("publish_every requires a publish_dir")
        opt = self.plan.optimizations
        up = opt.use_pallas
        learner, spec, C = self.learner, self.spec, self.C
        committee = C if self.plan.algorithm == "distboost_f" else None
        # Full-partition init: the vmapped fit cache and uniform weights
        # are exactly the fused path's; afterwards this process only ever
        # touches its own shard (and the replicated weights/masks).
        state = boosting.init_boost_state(
            learner, spec, rounds, self.masks, self.key,
            committee_size=committee, X=self._Xs,
        )
        self._Xs = None
        self.cache_i = (
            jax.tree.map(lambda x: x[self.pidx], state.fit_cache)
            if state.fit_cache is not None else None
        )
        cached = self.cache_i is not None and learner.fit_cached is not None

        # local single-collaborator fit (paper step 2) — bit-identical to
        # row pidx of the fused batched fit (batch-invariance, PR 3)
        def fit_one(Xi, yi, wi, ki, ci, dummy):
            if cached:
                return learner.fit_cached(spec, dummy, Xi, yi, wi, ki, ci)
            return learner.fit(spec, dummy, Xi, yi, wi, ki)

        jfit = jax.jit(fit_one)
        jpred = jax.jit(lambda hyps, Xi: scoring.predict_matrix(learner, spec, hyps, Xi))
        jerr = jax.jit(lambda p, yi, wi: scoring.shard_errors(p, yi, wi, use_pallas=up))
        jupd = jax.jit(lambda w, mis, mask, a: scoring.update_weights(
            w, mis, mask, a, use_pallas=up))
        jcomm_mis = jax.jit(lambda comm, Xi, yi: (
            boosting._committee_predict(learner, spec, comm, Xi) != yi
        ).astype(jnp.float32))

        alg = self.plan.algorithm
        pcache_i = None
        hyp_space = None
        if alg == "preweak_f":
            # Steps 1+2 once: T local-AdaBoost hypotheses from THIS shard,
            # then one setup gather assembles the C*T space (C-major, same
            # layout as preweak_f_setup's reshape).
            with trace.span("preweak.setup", rounds=rounds):
                keys = jax.random.split(state.key, C + 1)
                local_space = jax.jit(
                    lambda Xi, yi, mi, ki, ci: boosting._preweak_local_space(
                        learner, spec, Xi[None], yi[None], mi[None], ki[None],
                        jax.tree.map(lambda x: x[None], ci) if ci is not None else None,
                        rounds,
                    )
                )(self.Xi, self.yi, self.maski, keys[self.pidx], self.cache_i)  # [T, ...]
                gathered = self._gather_hyps(local_space, -1, label="preweak_space")
                hyp_space = jax.tree.map(
                    lambda x: x.reshape((C * rounds,) + x.shape[2:]), gathered
                )
                state = boosting.BoostState(
                    state.ensemble, state.weights, keys[-1], state.fit_cache
                )
                if opt.cache_predictions:
                    # static space -> predict THIS shard once, reduce every round
                    pcache_i = jpred(hyp_space, self.Xi)

        committee_pred = alg == "distboost_f"
        if opt.cache_predictions:
            tally = scoring.init_tally(self.X_test.shape[0], spec.n_classes)
            tally_fn = jax.jit(
                lambda ens, tl: scoring.tally_new_votes(
                    learner, spec, ens, tl, self.X_test, committee=committee_pred,
                )
            )

            def evaluate(state):
                nonlocal tally
                tally = tally_fn(state.ensemble, tally)
                return f1_macro(self.y_test, scoring.tally_predict(tally), spec.n_classes)
        else:
            predict = jax.jit(
                lambda ens, X: boosting.strong_predict(
                    learner, spec, ens, X, committee=committee_pred
                )
            )

            def evaluate(state):
                return f1_macro(self.y_test, predict(state.ensemble, self.X_test),
                                spec.n_classes)

        def fit_stage(state, r, wfit_row, kfit):
            keys = jax.random.split(kfit, C)
            dummy = learner.init(spec, keys[0])
            with trace.span("round.fit", round=r):
                h = jfit(self.Xi, self.yi, wfit_row, keys[self.pidx],
                         self.cache_i, dummy)
                jax.block_until_ready(h)  # keep fit time out of the collective span
            return h

        def append(ens, chosen, alpha):
            return boosting.Ensemble(
                params=boosting._set_slot(ens.params, ens.count, chosen),
                alpha=ens.alpha.at[ens.count].set(alpha),
                count=ens.count + 1,
            )

        def round_adaboost(state, r):
            key, kfit = jax.random.split(state.key)
            h_local = fit_stage(state, r, state.weights[self.pidx], kfit)
            hyps = self._gather_hyps(h_local, r)
            with trace.span("round.score", round=r):
                preds = jpred(hyps, self.Xi)  # [C, n_i] — predict ONCE
                local_errs = jerr(preds, self.yi, state.weights[self.pidx])
                jax.block_until_ready(local_errs)
            errs = jnp.asarray(
                self._gather(local_errs, span_name="round.exchange", r=r, label="errors")
            )  # [C, C]
            # replicated aggregation (paper step 4): same order of
            # operations as the fused aggregate stage -> same bits
            eps = jnp.sum(errs, axis=0)
            c = jnp.argmin(eps)
            alpha = boosting._samme_alpha(eps[c], spec.n_classes)
            local_mis = scoring.chosen_mis(preds, self.yi, c)
            mis = jnp.asarray(
                self._gather(local_mis, span_name="round.exchange", r=r, label="mis")
            )  # [C, n]
            with trace.span("round.aggregate", round=r):
                w = jupd(state.weights, mis, self.masks, alpha)
                ens = append(state.ensemble, boosting._take_slot(hyps, c), alpha)
            metrics = {"epsilon": eps[c], "alpha": alpha, "chosen": c.astype(jnp.int32)}
            return boosting.BoostState(ens, w, key, state.fit_cache), metrics

        def round_distboost(state, r):
            key, kfit = jax.random.split(state.key)
            h_local = fit_stage(state, r, state.weights[self.pidx], kfit)
            hyps = self._gather_hyps(h_local, r, label="committee")
            with trace.span("round.score", round=r):
                local_mis = jcomm_mis(hyps, self.Xi, self.yi)
                jax.block_until_ready(local_mis)
            mis = jnp.asarray(
                self._gather(local_mis, span_name="round.exchange", r=r, label="mis")
            )
            with trace.span("round.aggregate", round=r):
                eps = jnp.sum(state.weights * mis)
                alpha = boosting._samme_alpha(eps, spec.n_classes)
                w = jupd(state.weights, mis, self.masks, alpha)
                ens = append(state.ensemble, hyps, alpha)  # slot = whole committee
            metrics = {"epsilon": eps, "alpha": alpha, "chosen": jnp.zeros((), jnp.int32)}
            return boosting.BoostState(ens, w, key, state.fit_cache), metrics

        def round_bagging(state, r):
            key, kfit, kpick = jax.random.split(state.key, 3)
            wfit = self.maski / jnp.maximum(jnp.sum(self.maski), 1.0)  # local-uniform
            h_local = fit_stage(state, r, wfit, kfit)
            hyps = self._gather_hyps(h_local, r)
            with trace.span("round.aggregate", round=r):
                c = jax.random.randint(kpick, (), 0, C)  # replicated pick
                ens = append(state.ensemble, boosting._take_slot(hyps, c),
                             jnp.ones(()))
            metrics = {"epsilon": jnp.zeros(()), "alpha": jnp.ones(()),
                       "chosen": c.astype(jnp.int32)}
            return boosting.BoostState(ens, state.weights, key, state.fit_cache), metrics

        def round_preweak(state, r):
            with trace.span("round.score", round=r):
                preds = (pcache_i if pcache_i is not None
                         else jpred(hyp_space, self.Xi))  # [C*T, n_i]
                local_errs = jerr(preds, self.yi, state.weights[self.pidx])
                jax.block_until_ready(local_errs)
            errs = jnp.asarray(
                self._gather(local_errs, span_name="round.exchange", r=r, label="errors")
            )  # [C, C*T]
            eps = jnp.sum(errs, axis=0)
            c = jnp.argmin(eps)
            alpha = boosting._samme_alpha(eps[c], spec.n_classes)
            local_mis = scoring.chosen_mis(preds, self.yi, c)
            mis = jnp.asarray(
                self._gather(local_mis, span_name="round.exchange", r=r, label="mis")
            )
            with trace.span("round.aggregate", round=r):
                w = jupd(state.weights, mis, self.masks, alpha)
                ens = append(state.ensemble, boosting._take_slot(hyp_space, c), alpha)
            metrics = {"epsilon": eps[c], "alpha": alpha, "chosen": c.astype(jnp.int32)}
            return boosting.BoostState(ens, w, state.key, state.fit_cache), metrics

        round_fn = {
            "adaboost_f": round_adaboost,
            "distboost_f": round_distboost,
            "bagging": round_bagging,
            "preweak_f": round_preweak,
        }[alg]

        self._row_marker = (time.perf_counter(), self.comm_bytes, 0)
        for r in range(rounds):
            with trace.span("round", round=r, algorithm=alg,
                            process=self.pidx, processes=C):
                state, metrics = round_fn(state, r)
                _M_ROUNDS.inc()
                if (r + 1) % eval_every == 0 or r == rounds - 1:
                    row = {"round": r}
                    if is_main():
                        with trace.span("round.eval", round=r):
                            # once per eval_every: syncing IS the eval output
                            row["f1"] = float(evaluate(state))  # mafl: allow[host-sync]
                    row.update({k: float(v) for k, v in metrics.items()})  # mafl: allow[host-sync]
                    row.update(self._history_extras(r))
                    self.history.append(row)
                if publish_every and ((r + 1) % publish_every == 0 or r == rounds - 1):
                    if is_main():
                        with trace.span("round.publish", round=r):
                            self._publish_checkpoint(state, r, publish_dir, on_checkpoint)
        self.state = state
        return self.history

    def summary(self) -> Dict[str, Any]:
        """Run metadata for --history-out / the scaling bench."""
        return {
            "processes": self.C,
            "process": self.pidx,
            "algorithm": self.plan.algorithm,
            "packed_broadcast": self.packed_broadcast,
            "comm_bytes": self.comm_bytes,
            "collective_calls": self.collective_calls,
            "comm_breakdown": dict(self.comm_breakdown),
            "history": self.history,
        }
