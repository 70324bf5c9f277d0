"""MAFL federation runner — the paper's main entry point.

  PYTHONPATH=src python -m repro.launch.fl_run --dataset adult --rounds 100 \
      --collaborators 8 --learner decision_tree --algorithm adaboost_f

  # heterogeneous federation: cycle learner types across collaborators
  PYTHONPATH=src python -m repro.launch.fl_run --dataset adult --rounds 100 \
      --collaborators 8 --learners decision_tree,ridge,gaussian_nb

Modes:
  default       — fused jit round (all §5.1 optimisations on)
  --faithful    — interpreted OpenFL-style round (serialization + TensorDB +
                  polling barriers), the pre-optimisation behaviour
  --sharded     — SPMD shard_map round over the host mesh (requires >1 device)
  --learners    — comma-separated registry keys cycled across collaborators
                  (heterogeneous federation; fused mode only)
  --distributed — process-per-collaborator runtime over jax.distributed
                  collectives (one fl_run per process; see
                  ``launch/fl_spawn.py`` for the local N-process launcher)
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.core.hetero import HeterogeneousSpec
from repro.core.metrics import f1_macro
from repro.core.plan import OptimizationFlags, adaboost_plan, bagging_plan, fedavg_plan
from repro.data import get_dataset
from repro.fl.federation import Federation
from repro.fl.partition import partition
from repro.launch import jax_cache
from repro.learners import LearnerSpec
from repro.obs import metrics as obs_metrics, trace


def default_hparams(name: str, depth: int = 4) -> dict:
    """Per-family CLI defaults (shared by fl_run/serve_fl/--learners)."""
    if name in ("decision_tree", "extra_tree"):
        return {"depth": depth, "n_bins": 16}
    if name == "mlp":
        return {"hidden": 64, "steps": 200, "local_steps": 20}
    return {}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="adult")
    ap.add_argument("--algorithm", default="adaboost_f",
                    choices=["adaboost_f", "distboost_f", "preweak_f", "bagging", "fedavg"])
    ap.add_argument("--learner", default="decision_tree")
    ap.add_argument("--learners", default=None,
                    help="comma-separated learner registry keys cycled across "
                         "collaborators (e.g. decision_tree,ridge,gaussian_nb) — "
                         "a heterogeneous federation; overrides --learner")
    ap.add_argument("--collaborators", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--split", default="iid", choices=["iid", "dirichlet"])
    ap.add_argument("--dirichlet-alpha", type=float, default=0.5)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--faithful", action="store_true")
    ap.add_argument("--use-pallas", action="store_true",
                    help="route the step-3/4 scoring through the Pallas "
                         "kernels: compiled on a TPU, interpret mode on the "
                         "CPU (slow at large shapes); off the TPU it also "
                         "runs the tree-fit histogram kernel interpreted, "
                         "which the TPU runs on every fit path anyway")
    ap.add_argument("--sharded", action="store_true")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record host spans (round, round.eval, "
                         "round.publish; round.fit/score/aggregate only "
                         "under --distributed, where they are host phases) "
                         "and write a Chrome-trace JSON loadable in Perfetto "
                         "or chrome://tracing; also prints a phase-time "
                         "summary table. The spans also land on a JAX "
                         "profiler capture, beside the fused round's "
                         "fit/hist/score/aggregate/eval named scopes")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the process metrics registry (counters/gauges/"
                         "histograms) in Prometheus text exposition format")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--distributed", action="store_true",
                    help="process-per-collaborator runtime: this process is "
                         "collaborator --process-id of a --num-processes "
                         "federation exchanging rounds over real collectives")
    ap.add_argument("--coordinator", default="127.0.0.1:9781", metavar="HOST:PORT",
                    help="jax.distributed coordinator address (process 0 hosts it)")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--no-packed-broadcast", action="store_true",
                    help="gather the hypothesis pytree leaf-by-leaf instead of "
                         "as one packed wire buffer (the ±packed_broadcast "
                         "ablation of BENCH_distributed.json)")
    ap.add_argument("--publish-every", type=int, default=None, metavar="K",
                    help="publish a versioned serving artifact every K rounds "
                         "(process 0 in distributed mode)")
    ap.add_argument("--publish-dir", default=None,
                    help="directory for the rolling artifact stream")
    ap.add_argument("--history-out", default=None, metavar="PATH",
                    help="write the run history + comm accounting as JSON "
                         "(process 0 in distributed mode)")
    # -- elastic runtime (fl/elastic.py): participation policy ------------
    ap.add_argument("--elastic", action="store_true",
                    help="event-driven elastic rounds: straggler deadlines, "
                         "partial participation, staleness-discounted late "
                         "merges (with --distributed: the fault-tolerant "
                         "TCP-star runtime with dead-process eviction)")
    ap.add_argument("--deadline-ms", type=float, default=None, metavar="MS",
                    help="straggler deadline per round; omit to wait for "
                         "every active collaborator (lockstep semantics)")
    ap.add_argument("--min-responders", type=int, default=1,
                    help="a round never closes over fewer responders — the "
                         "deadline stretches to the fastest arrivals")
    ap.add_argument("--staleness-gamma", type=float, default=0.5,
                    help="late-merge alpha discount per round of lateness")
    ap.add_argument("--max-staleness", type=int, default=2,
                    help="rounds after which a late hypothesis is discarded")
    ap.add_argument("--no-late-merge", action="store_true",
                    help="drop stragglers' uploads instead of merging them")
    ap.add_argument("--elastic-realtime", action="store_true",
                    help="wall-clock arrival board (timers) instead of the "
                         "deterministic virtual clock (in-process runs only)")
    # -- fault injection (fl/elastic.py::FaultPlan) -----------------------
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the deterministic fault schedule")
    ap.add_argument("--fault-drop-p", type=float, default=0.0,
                    help="per-(round, collaborator) upload-loss probability")
    ap.add_argument("--fault-delay-p", type=float, default=0.0,
                    help="per-(round, collaborator) straggler probability")
    ap.add_argument("--fault-delay-ms", default="0:0", metavar="LO:HI",
                    help="straggler delay range in milliseconds")
    ap.add_argument("--fault-kill", action="append", default=[],
                    metavar="PID:ROUND",
                    help="kill collaborator PID at ROUND (repeatable); in "
                         "distributed mode the process really exits mid-round")
    ap.add_argument("--fault-flaky", action="append", default=[],
                    metavar="PID:OFF:REJOIN",
                    help="collaborator PID offline for rounds [OFF, REJOIN) "
                         "then rejoins (repeatable)")
    args = ap.parse_args(argv)
    jax_cache.use_persistent_cache()
    if args.distributed:
        # must precede every other JAX call in the process: pins it to the
        # CPU, picks the gloo CPU collective backend and joins the
        # coordinator's process group (the elastic TCP star only pins)
        if args.faithful or args.sharded or args.learners:
            ap.error("--distributed replaces --faithful/--sharded and is "
                     "homogeneous-only (no --learners)")
        if args.algorithm == "fedavg":
            ap.error("--distributed covers the MAFL boosting algorithms, not fedavg")
        if args.collaborators != args.num_processes:
            ap.error(f"--distributed is process-per-collaborator: "
                     f"--collaborators {args.collaborators} != "
                     f"--num-processes {args.num_processes}")
        from repro.fl import distributed as _dist

        if args.elastic:
            _dist.pin_cpu()
        else:
            _dist.initialize(args.coordinator, args.num_processes, args.process_id)
    if args.trace:
        trace.enable()

    key = jax.random.PRNGKey(args.seed)
    k1, k2, k3 = jax.random.split(key, 3)
    dspec, (Xtr, ytr, Xte, yte) = get_dataset(args.dataset, k1)
    Xs, ys, masks = partition(
        args.split, Xtr, ytr, args.collaborators, k2,
        **({"alpha": args.dirichlet_alpha, "n_classes": dspec.n_classes}
           if args.split == "dirichlet" else {}),
    )
    if args.learners:
        names = [n.strip() for n in args.learners.split(",") if n.strip()]
        if args.sharded:
            ap.error("--learners is fused-mode only: the SPMD round runs one "
                     "program per device and cannot mix model structures")
        if args.faithful:
            ap.error("--learners is fused-mode only; drop --faithful")
        if args.algorithm == "fedavg":
            ap.error("fedavg averages parameters and cannot mix model families")
        lspec = HeterogeneousSpec.cycle(
            names, args.collaborators, dspec.n_features, dspec.n_classes,
            hparams={n: default_hparams(n, args.depth) for n in names},
        )
        print("heterogeneous federation:",
              {i: lspec.specs[g].name for i, g in enumerate(lspec.assignment)})
    else:
        lspec = LearnerSpec(
            args.learner, dspec.n_features, dspec.n_classes,
            default_hparams(args.learner, args.depth),
        )

    if args.distributed:
        return _run_distributed(args, lspec, Xs, ys, masks, Xte, yte, k3)

    if args.sharded:
        return _run_sharded(args, lspec, Xs, ys, masks, Xte, yte, k3)

    if args.algorithm == "fedavg":
        plan = fedavg_plan(rounds=args.rounds)
    elif args.algorithm == "bagging":
        plan = bagging_plan(rounds=args.rounds)
    else:
        plan = adaboost_plan(rounds=args.rounds, algorithm=args.algorithm)
    import dataclasses

    if args.faithful:
        plan = dataclasses.replace(
            plan,
            optimizations=OptimizationFlags(
                packed_serialization=False, bounded_tensordb=False,
                fast_barrier=False, fused_round=False,
                use_pallas=args.use_pallas, cache_predictions=False,
            ),
        )
    elif args.use_pallas:
        plan = dataclasses.replace(
            plan,
            optimizations=dataclasses.replace(plan.optimizations, use_pallas=True),
        )
    fed = Federation(plan, Xs, ys, masks, Xte, yte, lspec, k3)
    policy, faults = _build_policy_faults(args) if args.elastic else (None, None)
    t0 = time.time()
    history = fed.run(eval_every=args.eval_every,
                      publish_every=args.publish_every,
                      publish_dir=args.publish_dir,
                      policy=policy, faults=faults)
    dt = time.time() - t0
    _print_history(history)
    print(f"total {dt:.1f}s  comm {fed.comm_bytes/1e6:.2f} MB  final F1 {history[-1]['f1']:.4f}")
    if args.history_out:
        import json

        summary = {"history": history, "comm_bytes": fed.comm_bytes}
        if args.elastic:
            summary = fed.elastic.summary()
        with open(args.history_out, "w") as f:
            json.dump(summary, f, indent=2)
    _finish_obs(args)
    return history


def _build_policy_faults(args):
    """--elastic / --fault-* flags -> (ParticipationPolicy, FaultPlan)."""
    from repro.fl.elastic import FaultPlan, ParticipationPolicy

    lo, hi = (float(x) for x in args.fault_delay_ms.split(":"))
    kills = tuple(
        tuple(int(x) for x in spec.split(":")) for spec in args.fault_kill
    )
    flaky = tuple(
        tuple(int(x) for x in spec.split(":")) for spec in args.fault_flaky
    )
    policy = ParticipationPolicy(
        deadline_s=None if args.deadline_ms is None else args.deadline_ms / 1e3,
        min_responders=args.min_responders,
        staleness_gamma=args.staleness_gamma,
        max_staleness=args.max_staleness,
        late_merge=not args.no_late_merge,
        realtime=args.elastic_realtime,
    )
    faults = FaultPlan(
        seed=args.fault_seed,
        delay_p=args.fault_delay_p,
        delay_range_s=(lo / 1e3, hi / 1e3),
        drop_p=args.fault_drop_p,
        kills=kills,
        flaky=flaky,
    )
    return policy, faults


def _print_history(history):
    for h in history:
        extra = ""
        if "round_seconds" in h:
            extra = (f"  {1e3 * h['round_seconds']:8.1f} ms/round"
                     f"  {h.get('comm_bytes', 0) / 1e3:9.1f} kB")
        print(f"round {h['round']:4d}  f1 {h['f1']:.4f}  "
              f"alpha {h.get('alpha', 0):.3f}{extra}")


def _run_distributed(args, lspec, Xs, ys, masks, Xte, yte, key):
    """One process of the process-per-collaborator federation (the local
    N-process launch lives in ``launch/fl_spawn.py``)."""
    import dataclasses
    import json

    if args.elastic:
        from repro.fl.elastic_dist import run_elastic_distributed

        policy, faults = _build_policy_faults(args)
        t0 = time.time()
        coord, history = run_elastic_distributed(
            args, policy, faults, lspec, Xs, ys, masks, Xte, yte, key,
        )
        if coord is not None:  # process 0
            dt = time.time() - t0
            _print_history(history)
            print(f"elastic distributed ({args.num_processes} processes, "
                  f"evicted {len(coord.evicted)}): total {dt:.1f}s  "
                  f"comm {coord.comm_bytes/1e6:.2f} MB  "
                  f"final F1 {history[-1]['f1']:.4f}")
            if args.history_out:
                with open(args.history_out, "w") as f:
                    json.dump(coord.summary(), f, indent=2)
            _finish_obs(args)
        return history

    from repro.fl.distributed import DistributedFederation, is_main

    plan = (bagging_plan(rounds=args.rounds) if args.algorithm == "bagging"
            else adaboost_plan(rounds=args.rounds, algorithm=args.algorithm))
    if args.use_pallas:
        plan = dataclasses.replace(
            plan,
            optimizations=dataclasses.replace(plan.optimizations, use_pallas=True),
        )
    fed = DistributedFederation(
        plan, Xs, ys, masks, Xte, yte, lspec, key,
        packed_broadcast=not args.no_packed_broadcast,
    )
    t0 = time.time()
    history = fed.run(
        eval_every=args.eval_every,
        publish_every=args.publish_every, publish_dir=args.publish_dir,
    )
    dt = time.time() - t0
    if is_main():
        _print_history(history)
        print(f"distributed ({fed.C} processes, "
              f"{'packed' if fed.packed_broadcast else 'per-leaf'} broadcast): "
              f"total {dt:.1f}s  comm {fed.comm_bytes/1e6:.2f} MB  "
              f"final F1 {history[-1]['f1']:.4f}")
        if args.history_out:
            with open(args.history_out, "w") as f:
                json.dump(fed.summary(), f, indent=2)
        _finish_obs(args)
    return history


def _finish_obs(args):
    """Export the trace / metrics dump the run accumulated (shared by
    fl_run and serve_fl: both expose --trace/--metrics-out)."""
    if getattr(args, "trace", None):
        trace.export(args.trace)
        print(trace.format_summary("phase-time summary"))
        print(f"trace written to {args.trace} "
              "(open in Perfetto or chrome://tracing)")
    if getattr(args, "metrics_out", None):
        obs_metrics.dump(args.metrics_out)
        print(f"metrics written to {args.metrics_out} (Prometheus text format)")


def _run_sharded(args, lspec, Xs, ys, masks, Xte, yte, key):
    import jax.numpy as jnp

    from repro.core import boosting
    from repro.fl.sharded import (
        federation_mesh, sharded_adaboost_round, sharded_strong_predict,
    )
    from repro.learners import get_learner

    n_dev = len(jax.devices())
    C = Xs.shape[0]
    mesh = federation_mesh(C)
    learner = get_learner(lspec.name)
    # X=Xs: shard-static fit precomputation (BinnedDataset for trees) is
    # built once here and consumed inside the shard_map round.
    state = boosting.init_boost_state(learner, lspec, args.rounds, masks, key, X=Xs)
    with jax.set_mesh(mesh):
        rfn = jax.jit(
            lambda s, X, y, m: sharded_adaboost_round(
                learner, lspec, mesh, s, X, y, m, use_pallas=args.use_pallas
            )
        )
        t0 = time.time()
        for r in range(args.rounds):
            state, metrics = rfn(state, Xs, ys, masks)
        n = Xte.shape[0] - Xte.shape[0] % C
        pred = sharded_strong_predict(learner, lspec, mesh, state.ensemble, Xte[:n])
        dt = time.time() - t0
    f1 = float(f1_macro(yte[:n], pred, lspec.n_classes))
    print(f"sharded ({C} collaborators on {n_dev} devices): {dt:.1f}s  F1 {f1:.4f}")
    return f1


if __name__ == "__main__":
    main()
