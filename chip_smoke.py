"""Bring-up smoke on the TPU: the main path once, through the entry
points a user calls, at the paper's headline deployment — AdaBoost.F on
the ``adult`` shape at its real row count (32,561 train / 16,281 test
rows, 14 features, K=2), C=8 collaborators, depth-4 oblivious trees
with 16 bins.  Data comes from ``--seed`` through ``data/synthetic.py``.

  python chip_smoke.py               # one chip: train twice, then serve
  python chip_smoke.py --four-chips  # four chips: the sharded round only

One chip: ``Federation.run`` for a few rounds, built as ``fl_run`` builds
it, once on the jnp oracle path and once with the Pallas kernels; the
two runs must agree under the cross-path contract
(``core/scoring.CROSS_PATH_RTOL``: the same chosen hypothesis every
round, alphas within that relative bound) and on final macro-F1 within
``F1_ATOL``.  The Pallas run's ensemble then goes through
``save_artifact`` -> ``ServeEngine.from_artifact(use_pallas=True)``: the
whole test split through the sync path and a few lone requests through
the deadline scheduler must equal ``boosting.strong_predict`` except at
vote ties (the top two totals of an f64 tally within f32 rounding of
each other), which are counted.

Four chips: ``sharded_adaboost_round`` + ``sharded_strong_predict`` on a
``(4, 1)`` mesh with C=4 (the ``fl_run --sharded`` path), compared with
the fused single-device round of the same configuration.

Every phase runs in this one process, which holds the chip.  No phase
catches an error and carries on.  Without a TPU the script exits
non-zero before any phase.  Timings printed here are bring-up
observations, not benchmark results.  The last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

DATASET = "adult"
ROUNDS = 5
DEPTH = 4
N_BINS = 16
BATCH = 256
F1_ATOL = 1e-3  # final macro-F1, kernel vs oracle and sharded vs fused


def require_tpu():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}"
        )
    return devices


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (monitoring events)."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs


class InterpretRecorder:
    """Records the interpret flag of every Pallas kernel dispatch."""

    def __init__(self):
        from repro.kernels import ops

        self.flags = []
        decide = ops._interpret

        def recorded():
            self.flags.append(decide())
            return self.flags[-1]

        ops._interpret = recorded


def build_data(n_collaborators: int, seed: int, dataset: str = DATASET):
    """Data, partition and learner spec exactly as ``fl_run`` builds them."""
    import jax

    from repro.data import get_dataset
    from repro.fl.partition import partition
    from repro.launch.fl_run import default_hparams
    from repro.learners import LearnerSpec

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    dspec, (Xtr, ytr, Xte, yte) = get_dataset(dataset, k1)
    Xs, ys, masks = partition("iid", Xtr, ytr, n_collaborators, k2)
    hp = {**default_hparams("decision_tree", DEPTH), "n_bins": N_BINS}
    lspec = LearnerSpec("decision_tree", dspec.n_features, dspec.n_classes, hp)
    return {"dataset": dataset, "Xs": Xs, "ys": ys, "masks": masks, "Xte": Xte,
            "yte": yte, "lspec": lspec, "key": k3}


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def train(data, *, use_pallas: bool, rounds: int, clock: CompileClock):
    """``Federation.run`` with the plan ``fl_run`` builds (``--use-pallas``
    toggles the kernels); returns the federation and its history."""
    import dataclasses

    from repro.core.plan import adaboost_plan
    from repro.fl.federation import Federation

    plan = adaboost_plan(rounds=rounds, algorithm="adaboost_f")
    if use_pallas:
        plan = dataclasses.replace(
            plan, optimizations=dataclasses.replace(plan.optimizations, use_pallas=True),
        )
    fed = Federation(plan, data["Xs"], data["ys"], data["masks"],
                     data["Xte"], data["yte"], data["lspec"], data["key"])
    c0, t0 = clock.seconds, time.perf_counter()
    history = fed.run(eval_every=1)
    wall = time.perf_counter() - t0
    name = "pallas" if use_pallas else "oracle"
    secs = [h["round_seconds"] for h in history]
    print(f"[train/{name}] wall {wall:.3f}s  compile {clock.seconds - c0:.3f}s  "
          f"round_seconds {[round(s, 4) for s in secs]}  "
          f"steady median {statistics.median(secs[1:] or secs):.4f}s  "
          f"peak_bytes_in_use {peak_bytes()}")
    for h in history:
        print(f"[train/{name}] round {h['round']}  chosen {int(h['chosen'])}  "
              f"alpha {h['alpha']!r}  f1 {h['f1']!r}")
    return fed, history


def compare_runs(ref_hist, got_hist, what: str):
    """The cross-path contract: identical choices, alphas within
    CROSS_PATH_RTOL, final F1 within F1_ATOL."""
    import numpy as np

    from repro.core.scoring import CROSS_PATH_RTOL

    chosen_ref = [int(h["chosen"]) for h in ref_hist]
    chosen_got = [int(h["chosen"]) for h in got_hist]
    if chosen_ref != chosen_got:
        raise AssertionError(f"{what}: chosen hypotheses differ {chosen_ref} vs {chosen_got}")
    a_ref = np.array([h["alpha"] for h in ref_hist])
    a_got = np.array([h["alpha"] for h in got_hist])
    rel = float(np.max(np.abs(a_got - a_ref) / np.abs(a_ref)))
    if not rel <= CROSS_PATH_RTOL:
        raise AssertionError(f"{what}: alpha rel err {rel} > {CROSS_PATH_RTOL}")
    df1 = abs(got_hist[-1]["f1"] - ref_hist[-1]["f1"])
    if not df1 <= F1_ATOL:
        raise AssertionError(f"{what}: final F1 differs by {df1} > {F1_ATOL}")
    print(f"[compare/{what}] chosen identical {chosen_ref}  alpha max rel err {rel!r} "
          f"(bound {CROSS_PATH_RTOL})  final F1 {ref_hist[-1]['f1']!r} vs "
          f"{got_hist[-1]['f1']!r} (|d| {df1!r}, bound {F1_ATOL})")


def round_kernel_calls(data, rounds: int) -> int:
    """Compile the Pallas fused round and count its Mosaic kernels."""
    import jax

    from repro.core import boosting
    from repro.learners import get_learner

    lspec = data["lspec"]
    learner = get_learner(lspec.name)
    state = boosting.init_boost_state(
        learner, lspec, rounds, data["masks"], data["key"], X=data["Xs"]
    )
    text = jax.jit(
        lambda s, X, y, m: boosting.adaboost_f_round(
            learner, lspec, s, X, y, m, use_pallas=True
        )
    ).lower(state, data["Xs"], data["ys"], data["masks"]).compile().as_text()
    n = text.count('custom_call_target="tpu_custom_call"')
    # depth tree_hist levels + weighted_errors + weight_update
    if n < DEPTH + 2:
        raise AssertionError(f"compiled Pallas round holds {n} tpu_custom_call (< {DEPTH + 2})")
    print(f"[kernels] compiled Pallas round holds {n} tpu_custom_call")
    return n


def tree_hist_error(data, level: int = DEPTH - 1):
    """One level's histogram, kernel vs oracle, on the round's fit cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.learners import get_learner
    from repro.learners.base import weighted_onehot

    lspec = data["lspec"]
    learner = get_learner(lspec.name)
    cache = jax.vmap(lambda Xi: learner.precompute(lspec, Xi))(data["Xs"])
    masks = data["masks"]
    w = masks / jnp.sum(masks)
    wy = jax.vmap(lambda yi, wi: weighted_onehot(yi, wi, lspec.n_classes))(data["ys"], w)
    n_leaves = 2**level
    leaf = jax.random.randint(jax.random.PRNGKey(level), w.shape, 0, n_leaves)
    hist = jax.jit(
        lambda b, l, v, up: ops.tree_hist(
            b, l, v, n_leaves=n_leaves, n_bins_p1=N_BINS + 1, use_pallas=up
        ),
        static_argnums=3,
    )
    got = np.asarray(hist(cache.bin_idx, leaf, wy, True))
    want = np.asarray(hist(cache.bin_idx, leaf, wy, False))
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    if not err <= 1e-5 * scale:
        raise AssertionError(f"tree_hist max abs err {err} > 1e-5 * {scale}")
    print(f"[kernels] tree_hist level {level} {tuple(got.shape)}: max abs err {err!r} "
          f"(max |value| {scale!r})")
    return err


def vote_ties(learner, lspec, ens, X):
    """Rows whose top-two alpha-weighted vote totals lie within the f32
    rounding bound of each other, from an f64 tally."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import scoring

    T = int(ens.alpha.shape[0])
    preds = np.asarray(jax.jit(jax.vmap(
        lambda t: scoring.member_prediction(learner, lspec, scoring._take_slot(ens.params, t), X)
    ))(jnp.arange(T)))  # [T, n]
    used = np.where(np.arange(T) < int(ens.count), np.asarray(ens.alpha, np.float64), 0.0)
    votes = np.zeros((X.shape[0], lspec.n_classes))
    for t in range(T):
        votes[np.arange(X.shape[0]), preds[t]] += used[t]
    top2 = np.sort(votes, axis=1)[:, -2:]
    tol = T * np.finfo(np.float32).eps * np.sum(np.abs(used))
    return top2[:, 1] - top2[:, 0] <= tol


def serve(fed, data, recorder: InterpretRecorder, clock: CompileClock, n_lone: int = 5):
    """save_artifact -> from_artifact(use_pallas) -> sync + deadline paths."""
    import jax
    import numpy as np

    from repro.core import boosting
    from repro.serve import EngineConfig, ServeEngine, load_artifact, save_artifact

    lspec, ens = data["lspec"], fed._fused_state.ensemble
    Xte = np.asarray(data["Xte"])
    with tempfile.TemporaryDirectory() as tmp:
        path = save_artifact(Path(tmp) / "adult_adaboost_f.mafl", lspec, ens)
        art = load_artifact(path)
    engine = ServeEngine.from_artifact(
        art, config=EngineConfig(batch_size=BATCH, use_pallas=True)
    )
    calls0, c0, t0 = len(recorder.flags), clock.seconds, time.perf_counter()
    got = engine.predict(Xte)
    wall = time.perf_counter() - t0
    if len(recorder.flags) == calls0:
        raise AssertionError("serving dispatched no Pallas kernel")
    want = np.asarray(jax.jit(
        lambda e, X: boosting.strong_predict(art.learner, lspec, e, X)
    )(art.ensemble, data["Xte"]))
    ties = vote_ties(art.learner, lspec, art.ensemble, data["Xte"])
    diff = got != want
    if got.shape != want.shape or np.any(diff & ~ties):
        raise AssertionError(
            f"served != strong_predict on {int(np.sum(diff & ~ties))} non-tie rows"
        )
    print(f"[serve/sync] {Xte.shape[0]} rows in {engine.stats.batches} batches of {BATCH} "
          f"({engine.stats.padded_rows} padded): wall {wall:.3f}s  compile "
          f"{clock.seconds - c0:.3f}s  mismatches {int(np.sum(diff))}  "
          f"vote ties {int(np.sum(ties))}  peak_bytes_in_use {peak_bytes()}")
    with engine.scheduler(t_max_s=0.005) as sched:
        lone = []
        for i in range(n_lone):
            t0 = time.perf_counter()
            (rid,) = sched.submit(Xte[i])
            lone.append((int(sched.result(rid, timeout_s=60.0)), time.perf_counter() - t0))
    for i, (p, _) in enumerate(lone):
        if p != want[i] and not ties[i]:
            raise AssertionError(f"deadline scheduler answered {p} != {want[i]} for row {i}")
    print(f"[serve/deadline] {n_lone} lone requests answered, latencies "
          f"{[round(s, 5) for _, s in lone]}s")


def one_chip(seed: int):
    clock = CompileClock()
    recorder = InterpretRecorder()
    data = build_data(8, seed)
    print(f"[data] {data['dataset']}: Xs {tuple(data['Xs'].shape)}  test {tuple(data['Xte'].shape)}  "
          f"K={data['lspec'].n_classes}  depth {DEPTH}  bins {N_BINS}")
    _, h_oracle = train(data, use_pallas=False, rounds=ROUNDS, clock=clock)
    fed, h_pallas = train(data, use_pallas=True, rounds=ROUNDS, clock=clock)
    compare_runs(h_oracle, h_pallas, "pallas vs oracle")
    round_kernel_calls(data, ROUNDS)
    tree_hist_error(data)
    serve(fed, data, recorder, clock)
    if not recorder.flags or any(recorder.flags):
        raise AssertionError(f"kernel dispatches with interpret=True: {recorder.flags}")
    print(f"[kernels] {len(recorder.flags)} Pallas dispatches, interpret=False on all")


def four_chips(seed: int):
    """The sharded round on a (4, 1) mesh vs the fused single-device round."""
    import jax

    from repro.core import boosting
    from repro.core.metrics import f1_macro
    from repro.fl.sharded import (
        federation_mesh, sharded_adaboost_round, sharded_strong_predict,
    )
    from repro.learners import get_learner

    C = 4
    if len(jax.devices()) != C:
        raise SystemExit(f"chip_smoke --four-chips: needs {C} chips, have {len(jax.devices())}")
    clock = CompileClock()
    data = build_data(C, seed)
    lspec = data["lspec"]
    learner = get_learner(lspec.name)
    Xs, ys, masks, Xte, yte = (data[k] for k in ("Xs", "ys", "masks", "Xte", "yte"))
    n = Xte.shape[0] - Xte.shape[0] % C
    init = lambda: boosting.init_boost_state(learner, lspec, ROUNDS, masks, data["key"], X=Xs)
    mesh = federation_mesh(C)
    print(f"[data] {data['dataset']}: Xs {tuple(Xs.shape)} on mesh {dict(mesh.shape)}")

    hists = {}
    with jax.set_mesh(mesh):
        rfn = jax.jit(lambda s, X, y, m: sharded_adaboost_round(
            learner, lspec, mesh, s, X, y, m, use_pallas=True))
        state, c0, t0, rows = init(), clock.seconds, time.perf_counter(), []
        for _ in range(ROUNDS):
            state, m = rfn(state, Xs, ys, masks)
            rows.append({"chosen": int(m["chosen"]), "alpha": float(m["alpha"])})
        pred = sharded_strong_predict(learner, lspec, mesh, state.ensemble, Xte[:n],
                                      use_pallas=True)
        rows[-1]["f1"] = float(f1_macro(yte[:n], pred, lspec.n_classes))
        wall = time.perf_counter() - t0
    shards = sorted((s.device.id, s.index[0].start) for s in state.weights.addressable_shards)
    if len({d for d, _ in shards}) != C or len({i for _, i in shards}) != C:
        raise AssertionError(f"collaborator shards not one per chip: {shards}")
    print(f"[sharded] weights shards (device id, collaborator row): {shards}")
    print(f"[sharded] {ROUNDS} rounds + predict: wall {wall:.3f}s  compile "
          f"{clock.seconds - c0:.3f}s  peak_bytes_in_use {peak_bytes()}")
    hists["sharded"] = rows

    rfn = jax.jit(lambda s, X, y, m: boosting.adaboost_f_round(
        learner, lspec, s, X, y, m, use_pallas=True))
    state, c0, t0, rows = init(), clock.seconds, time.perf_counter(), []
    for _ in range(ROUNDS):
        state, m = rfn(state, Xs, ys, masks)
        rows.append({"chosen": int(m["chosen"]), "alpha": float(m["alpha"])})
    pred = boosting.strong_predict(learner, lspec, state.ensemble, Xte[:n])
    rows[-1]["f1"] = float(f1_macro(yte[:n], pred, lspec.n_classes))
    print(f"[fused] {ROUNDS} rounds + predict on {jax.devices()[0]}: wall "
          f"{time.perf_counter() - t0:.3f}s  compile {clock.seconds - c0:.3f}s")
    hists["fused"] = rows
    for name, rows in hists.items():
        print(f"[{name}] chosen {[r['chosen'] for r in rows]}  alpha "
              f"{[r['alpha'] for r in rows]}  F1 {rows[-1]['f1']!r}")
    compare_runs(hists["fused"], hists["sharded"], "sharded vs fused")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded round on four chips vs the fused round")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = require_tpu()
    from repro.launch import jax_cache

    jax_cache.use_persistent_cache()
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
