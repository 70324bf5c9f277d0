"""Ensemble serving driver — train-then-serve, load-then-serve, or the
continuous train→publish→serve loop.

  # train a federation, save the artifact, then serve the test split:
  PYTHONPATH=src python -m repro.launch.serve_fl --dataset pendigits \
      --learner decision_tree --rounds 10 --artifact /tmp/pendigits.mafl

  # serve an existing artifact:
  PYTHONPATH=src python -m repro.launch.serve_fl --dataset pendigits \
      --artifact /tmp/pendigits.mafl --load

  # continuous loop: the federation publishes a rolling artifact every
  # k rounds and the serving consumer folds each checkpoint in
  # incrementally (append-only growth — O(new members) per checkpoint):
  PYTHONPATH=src python -m repro.launch.serve_fl --dataset pendigits \
      --learner decision_tree --rounds 10 --publish-every 2 \
      --publish-dir /tmp/pendigits_pub

  # heterogeneous: cycle learner types across collaborators; the mixed
  # ensemble trains, publishes one v2 artifact (per-member learner keys
  # in the manifest) and serves behind the same engine API:
  PYTHONPATH=src python -m repro.launch.serve_fl --dataset pendigits \
      --learners decision_tree,ridge,gaussian_nb --collaborators 6 \
      --rounds 10 --publish-every 2 --publish-dir /tmp/pendigits_hetero

Serving drives the micro-batching engine over the test split (ragged
tail included) under the chosen dispatch policy — ``--policy sync``
(submit/flush) or ``--policy deadline`` (async dispatch loop: a partial
batch runs by itself after ``--t-max-ms``, no flush) — reports req/s
and p50/p99 latency, then replays the same traffic against the
shard-resident vote cache to show the cache-hit path.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core import boosting, hetero
from repro.core.hetero import HeterogeneousSpec
from repro.core.metrics import f1_macro
from repro.data import get_dataset
from repro.fl.partition import iid_partition
from repro.launch import jax_cache
from repro.launch.fl_run import _finish_obs, default_hparams
from repro.learners import LearnerSpec, get_learner
from repro.obs import trace
from repro.serve import ServeEngine, ShardVoteCache, load_artifact, save_artifact


def train_ensemble(args, lspec, learner, Xtr, ytr, key):
    """AdaBoost.F training loop for either spec flavour (``learner`` is
    None when ``lspec`` is a HeterogeneousSpec)."""
    Xs, ys, masks = iid_partition(Xtr, ytr, args.collaborators, key)
    if isinstance(lspec, HeterogeneousSpec):
        state = hetero.init_hetero_boost_state(
            lspec, args.rounds, masks, jax.random.fold_in(key, 1), X=Xs
        )
        rfn = jax.jit(
            lambda s: hetero.hetero_adaboost_f_round(
                lspec, s, Xs, ys, masks, use_pallas=args.use_pallas
            )
        )
    else:
        state = boosting.init_boost_state(
            learner, lspec, args.rounds, masks, jax.random.fold_in(key, 1), X=Xs
        )
        rfn = jax.jit(
            lambda s: boosting.adaboost_f_round(
                learner, lspec, s, Xs, ys, masks, use_pallas=args.use_pallas
            )
        )
    t0 = time.time()
    for _ in range(args.rounds):
        state, _ = rfn(state)
    jax.block_until_ready(state.weights)
    print(f"trained {args.rounds} rounds x {args.collaborators} collaborators "
          f"in {time.time() - t0:.1f}s")
    return state.ensemble


def _drive_engine(args, engine, Xte):
    """Push the ragged request stream through the configured policy;
    returns (predictions in submit order, wall seconds)."""
    step = args.request_rows
    if args.policy == "deadline":
        with engine.scheduler(t_max_s=args.t_max_ms / 1e3) as sched:
            t0 = time.perf_counter()
            ids = []
            for i in range(0, Xte.shape[0], step):
                ids.extend(sched.submit(np.asarray(Xte[i : i + step])))
            # NO flush: the tail dispatches on its own at the deadline
            pred = sched.results(ids, timeout_s=60.0)
            dt = time.perf_counter() - t0
        return pred, dt
    t0 = time.perf_counter()
    ids = []
    for i in range(0, Xte.shape[0], step):
        ids.extend(engine.submit(np.asarray(Xte[i : i + step])))
    engine.flush()
    dt = time.perf_counter() - t0
    return np.array([engine.take(i) for i in ids]), dt


def serve(args, learner, lspec, ensemble, Xte, yte, *, committee=False):
    engine = ServeEngine(
        learner, lspec, ensemble,
        batch_size=args.batch, committee=committee, use_pallas=args.use_pallas,
    )
    engine.warmup()  # compile cache warm before traffic arrives

    pred, dt = _drive_engine(args, engine, Xte)
    n = Xte.shape[0]
    f1 = float(f1_macro(yte, pred, lspec.n_classes))
    # request_latencies is a bounded log-spaced histogram: percentiles
    # carry a ~5% relative error (see obs/metrics.py), constant memory
    lat = engine.stats.request_latencies
    print(
        f"engine[{args.policy}]: {n} requests in {dt:.3f}s = {n/dt:.0f} req/s  "
        f"p50 {1e3*lat.percentile(50):.2f}ms p99 {1e3*lat.percentile(99):.2f}ms  "
        f"({engine.stats.batches} batches, {engine.stats.padded_rows} padded rows)  "
        f"F1 {f1:.4f}"
    )

    # repeat traffic: the shard-resident vote cache answers from the tally
    cache = ShardVoteCache(learner, lspec, ensemble, committee=committee)
    cache.predict("test_split", Xte)  # first contact builds the tally
    repeats = max(args.cache_repeats, 1)
    t0 = time.perf_counter()
    for _ in range(repeats):
        cache_pred = cache.predict("test_split")
    dt_hit = (time.perf_counter() - t0) / repeats
    assert np.array_equal(cache_pred, pred), "cache path diverged from engine"
    print(
        f"vote cache: repeat shard of {Xte.shape[0]} rows in {dt_hit*1e3:.2f}ms "
        f"= {Xte.shape[0]/dt_hit:.0f} req/s ({cache.stats()})"
    )
    return f1


def publish_and_consume(args, lspec, learner, Xtr, ytr, Xte, yte, key):
    """The continuous loop: a fused federation publishes a rolling
    artifact every ``--publish-every`` rounds, and the serving side
    (engine + vote cache) folds each checkpoint in incrementally."""
    import dataclasses

    from repro.core.plan import adaboost_plan
    from repro.fl.federation import Federation

    Xs, ys, masks = iid_partition(Xtr, ytr, args.collaborators, key)
    plan = adaboost_plan(rounds=args.rounds)
    if args.use_pallas:  # honour the flag for TRAINING too, not just serving
        plan = dataclasses.replace(
            plan,
            optimizations=dataclasses.replace(plan.optimizations, use_pallas=True),
        )
    fed = Federation(plan, Xs, ys, masks, Xte, yte, lspec, jax.random.fold_in(key, 1))

    engine = cache = None
    consumed = []  # (round, members, engine req/s) per checkpoint

    def consume(path, round_idx):
        nonlocal engine, cache
        art = load_artifact(path)
        if engine is None:  # first checkpoint: build the serving side
            engine = ServeEngine.from_artifact(
                art, batch_size=args.batch, use_pallas=args.use_pallas
            )
            engine.warmup()
            cache = ShardVoteCache.from_artifact(art)
        else:  # rolling checkpoint: a pure append — no recompile, no rebuild
            engine.update_ensemble(art.ensemble)
            cache.update_ensemble(art.ensemble)
        pred, dt = _drive_engine(args, engine, np.asarray(Xte))
        cache_pred = cache.predict("test_split", Xte)
        assert np.array_equal(cache_pred, pred), "cache diverged from engine"
        members = int(art.manifest["ensemble_count"])
        consumed.append((round_idx, members, Xte.shape[0] / dt))
        print(f"  checkpoint round {round_idx}: {members} members served, "
              f"{Xte.shape[0]/dt:.0f} req/s, cache {cache.stats()}")

    t0 = time.time()
    fed.run(
        rounds=args.rounds, eval_every=max(args.rounds // 2, 1),
        publish_every=args.publish_every, publish_dir=args.publish_dir,
        on_checkpoint=consume,
    )
    print(f"train+publish+serve loop: {len(fed.published)} checkpoints "
          f"in {time.time() - t0:.1f}s -> {args.publish_dir}")

    # the consumer only ever folded appended members: total folds == the
    # final member count (each member predicted exactly once per shard)
    final = load_artifact(fed.published[-1])
    assert cache.stats()["members_folded"] == int(final.manifest["ensemble_count"]), \
        cache.stats()
    assert engine.stats.compiles + engine.stats.cache_hits == 1, \
        "checkpoint swaps must not need new predict programs"
    if final.hetero:
        want = np.asarray(
            hetero.hetero_strong_predict(
                final.spec, final.ensemble, Xte, committee=final.committee
            )
        )
    else:
        want = np.asarray(
            boosting.strong_predict(final.learner, final.spec, final.ensemble, Xte)
        )
    got = cache.predict("test_split")
    np.testing.assert_array_equal(got, want)
    f1 = float(f1_macro(yte, got, lspec.n_classes))
    print(f"final checkpoint F1 {f1:.4f} (bit-for-bit strong_predict)")
    return f1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="pendigits")
    ap.add_argument("--learner", default="decision_tree")
    ap.add_argument("--learners", default=None,
                    help="comma-separated learner registry keys cycled across "
                         "collaborators — train/publish/serve a heterogeneous "
                         "federation; overrides --learner")
    ap.add_argument("--collaborators", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--artifact", default=None,
                    help="artifact path: written after training, or read with --load")
    ap.add_argument("--load", action="store_true",
                    help="skip training; serve the --artifact file")
    ap.add_argument("--publish-every", type=int, default=None,
                    help="train a federation that publishes a rolling artifact "
                         "every k rounds; serving consumes each checkpoint "
                         "incrementally (requires --publish-dir)")
    ap.add_argument("--publish-dir", default=None,
                    help="directory for the rolling artifact stream")
    ap.add_argument("--batch", type=int, default=256,
                    help="static serving batch size")
    ap.add_argument("--request-rows", type=int, default=37,
                    help="rows per submitted request (ragged on purpose)")
    ap.add_argument("--policy", choices=["sync", "deadline"], default="sync",
                    help="dispatch policy: sync submit/flush, or the async "
                         "deadline loop (partial batches run after --t-max-ms)")
    ap.add_argument("--t-max-ms", type=float, default=2.0,
                    help="deadline policy: max ms a partial batch may queue")
    ap.add_argument("--cache-repeats", type=int, default=10)
    ap.add_argument("--quantize", choices=["bf16", "int8"], default=None,
                    help="write the --artifact file with quantized leaf "
                         "payloads, calibrated on the served split so its "
                         "votes stay bit-identical to the f32 ensemble")
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record serve/compile/dispatch spans and write a "
                         "Chrome-trace JSON (Perfetto / chrome://tracing); "
                         "prints a phase-time summary table")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the process metrics registry (engine, "
                         "scheduler, registry, compile-cache and vote-cache "
                         "families) in Prometheus text exposition format")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    jax_cache.use_persistent_cache()
    if args.trace:
        trace.enable()

    key = jax.random.PRNGKey(args.seed)
    k1, k2 = jax.random.split(key)
    dspec, (Xtr, ytr, Xte, yte) = get_dataset(args.dataset, k1)

    def build_spec():
        if args.learners:
            names = [n.strip() for n in args.learners.split(",") if n.strip()]
            hspec = HeterogeneousSpec.cycle(
                names, args.collaborators, dspec.n_features, dspec.n_classes,
                hparams={n: default_hparams(n, args.depth) for n in names},
            )
            return hspec, None  # per-group learners live in the spec
        return (
            LearnerSpec(args.learner, dspec.n_features, dspec.n_classes,
                        default_hparams(args.learner, args.depth)),
            get_learner(args.learner),
        )

    if args.publish_every is not None:
        if not args.publish_dir:
            ap.error("--publish-every requires --publish-dir")
        lspec, learner = build_spec()
        f1 = publish_and_consume(args, lspec, learner, Xtr, ytr, Xte, yte, k2)
        _finish_obs(args)
        return f1

    committee = False
    if args.load:
        if not args.artifact:
            ap.error("--load requires --artifact")
        art = load_artifact(args.artifact)
        learner, lspec, ensemble = art.learner, art.spec, art.ensemble
        committee = art.committee  # DistBoost.F artifacts serve committees
        print(f"loaded {args.artifact}: {art.manifest['learner']} x "
              f"{art.manifest['ensemble_count']} members")
    else:
        lspec, learner = build_spec()
        ensemble = train_ensemble(args, lspec, learner, Xtr, ytr, k2)
        if args.artifact:
            p = save_artifact(args.artifact, lspec, ensemble,
                              extra={"dataset": args.dataset},
                              quantize=args.quantize,
                              calibrate=np.asarray(Xte) if args.quantize else None)
            print(f"saved artifact {p} ({p.stat().st_size} bytes"
                  + (f", {args.quantize} leaves" if args.quantize else "") + ")")
            if args.quantize:
                # a quantized artifact must serve the same votes it was
                # calibrated for — reload and serve the reloaded ensemble
                ensemble = load_artifact(p).ensemble

    f1 = serve(args, learner, lspec, ensemble, Xte, yte, committee=committee)
    _finish_obs(args)
    return f1


if __name__ == "__main__":
    main()
