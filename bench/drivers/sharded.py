"""Training through the SPMD round across chips, as ``fl_run --sharded``
runs it: ``fl/sharded.federation_mesh(C)`` with one collaborator per
chip, and the jitted ``sharded_adaboost_round`` called once per round
with the stacked shards.

Set-up builds the state and runs ``sync_every`` rounds, which compile.
The window then runs rounds in groups of ``sync_every``, blocking on the
state after each group, until ``--seconds`` have passed or the
ensemble's capacity runs out. ``round_s`` is the window over its rounds.

Outputs: the ensemble after the window (every member and alpha).
"""
from __future__ import annotations

import time

import numpy as np

from bench import compare
from bench.drivers import fused


def run(run):
    import jax

    from repro.core import boosting
    from repro.fl import sharded
    from repro.learners import get_learner

    spec, data, (Xs, ys, masks) = fused.setup(run)
    fed_cfg, tr = run.config["federation"], run.traffic
    cap, sync = fed_cfg["capacity"], tr["sync_every"]
    learner = get_learner(spec.name)
    mesh = sharded.federation_mesh(fed_cfg["collaborators"])
    state = boosting.init_boost_state(learner, spec, cap, masks, run.key("federation"), X=Xs)
    with jax.set_mesh(mesh):
        step = jax.jit(lambda s, X, y, m: sharded.sharded_adaboost_round(
            learner, spec, mesh, s, X, y, m))
        done = 0
        for _ in range(tr["warmup_syncs"]):
            for _ in range(sync):
                state, _ = step(state, Xs, ys, masks)
            jax.block_until_ready(state)
            done += sync
        setup_end = time.perf_counter()
        t0 = run.open_window(setup_end)
        start = done
        while done + sync <= cap:
            for _ in range(sync):
                state, _ = step(state, Xs, ys, masks)
            with run.annotate("bench.sync"):
                jax.block_until_ready(state)
            done += sync
            if time.perf_counter() - t0 >= run.seconds:
                break
        run.close_window()
    rounds = done - start
    ens = state.ensemble
    members = {k: np.asarray(v) for k, v in ens.params._asdict().items()}
    members["alpha"] = np.asarray(ens.alpha)
    outputs = {
        "members": members, "count": int(ens.count), "rounds_done": done,
        "data": [np.asarray(a) for a in data],
        "perm": fused.partition_perm(run, data[0].shape[0]),
    }
    del state, ens
    return {
        "setup_end": setup_end,
        "end_to_end": {"round_s": run.window_s / max(rounds, 1)},
        "rounds": rounds, "attempted": rounds, "failed": 0,
        "outputs": outputs,
    }


def check(run, out) -> dict:
    fed_cfg, data = run.config["federation"], run.config["data"]
    Xtr, ytr, Xte, yte = out["data"]
    return compare.training(
        out["members"], Xtr, ytr, out["perm"], fed_cfg, data["n_classes"],
        run.traffic["reference_rounds"], count=out["count"], rounds_done=out["rounds_done"],
        f1_row=None, X_test=Xte, y_test=yte,
    )
