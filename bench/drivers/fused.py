"""Training through ``Federation.run`` on the fused round, as ``fl_run``
runs it: one job covers set-up and the window.

The job publishes a serving checkpoint every ``publish_every`` rounds
(``Federation.run(publish_every=...)``, the train-while-serve deployment)
and evaluates every ``eval_every`` rounds. Its programs compile in its
first rounds; the window opens at the first checkpoint after the first
eval, when every program has run, and closes at the first checkpoint at
or after ``--seconds``, where the hook ends the job. ``round_s`` is the window over the rounds between the two.
Checkpoints go to a temporary directory, where the hook keeps only the
newest. The window also ends if the ensemble's capacity runs out.

Outputs: the last checkpoint (every member and alpha up to its round)
and the job's last eval row.
"""
from __future__ import annotations

import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import compare


class _WindowClosed(Exception):
    """Raised from the checkpoint hook to end the job at the window's close."""


def setup(run):
    """Data, partition and learner spec as ``fl_run`` builds them, from the
    configuration: (spec, (X_train, y_train, X_test, y_test), (Xs, ys, masks))."""
    from repro.fl.partition import partition
    from repro.learners import LearnerSpec

    fed_cfg, data = run.config["federation"], run.config["data"]
    Xtr, ytr, Xte, yte = run.data()
    Xs, ys, masks = partition(fed_cfg["partition"], Xtr, ytr, fed_cfg["collaborators"],
                              run.key("partition"))
    spec = LearnerSpec(fed_cfg["learner"], data["n_features"], data["n_classes"],
                       dict(fed_cfg["hparams"]))
    return spec, (Xtr, ytr, Xte, yte), (Xs, ys, masks)


def federation(run, spec, data, shards, rounds: int):
    """``Federation`` with the default plan, as ``fl_run`` builds it."""
    from repro.core.plan import adaboost_plan
    from repro.fl.federation import Federation

    plan = adaboost_plan(rounds=rounds, algorithm=run.config["federation"]["algorithm"])
    return Federation(plan, *shards, data[2], data[3], spec, run.key("federation"))


def run(run):
    spec, data, shards = setup(run)
    fed_cfg, tr = run.config["federation"], run.traffic
    fed = federation(run, spec, data, shards, fed_cfg["capacity"])
    publish_dir = tempfile.mkdtemp(prefix="bench_publish_")
    marks = []  # (time, round, path) per checkpoint since the window opened
    setup_end = []

    def on_checkpoint(path: Path, round_: int) -> None:
        with run.annotate("bench.checkpoint_hook"):
            now = time.perf_counter()
            if not marks and not fed.history:  # the eval has not run yet
                Path(path).unlink()
                return
            if not marks:
                setup_end.append(now)
                now = run.open_window(now)
            else:
                marks[-1][2].unlink()
            marks.append((now, round_, Path(path)))
            if now - marks[0][0] >= run.seconds:
                raise _WindowClosed

    try:
        fed.run(rounds=fed_cfg["capacity"], eval_every=tr["eval_every"],
                publish_every=tr["publish_every"], publish_dir=publish_dir,
                on_checkpoint=on_checkpoint)
    except _WindowClosed:
        pass
    t_end, r_end, last = marks[-1]
    run.close_window(t_end)
    rounds = r_end - marks[0][1]

    from repro.serve import load_artifact

    art = load_artifact(last)
    members = {k: np.asarray(v) for k, v in art.ensemble.params._asdict().items()}
    members["alpha"] = np.asarray(art.ensemble.alpha)
    count = int(art.ensemble.count)
    shutil.rmtree(publish_dir, ignore_errors=True)
    rows = [h for h in fed.history if h["round"] < r_end]
    outputs = {
        "members": members, "count": count, "rounds_done": r_end,
        "f1_row": {"round": rows[-1]["round"], "f1": rows[-1]["f1"]} if rows else None,
        "data": [np.asarray(a) for a in data],
        "perm": partition_perm(run, data[0].shape[0]),
    }
    del fed, art
    return {
        "setup_end": setup_end[0],
        "end_to_end": {"round_s": run.window_s / max(rounds, 1)},
        "rounds": rounds, "attempted": rounds, "failed": 0,
        "outputs": outputs,
    }


def partition_perm(run, n: int) -> np.ndarray:
    """The row order of the configuration's iid partition, for the reference."""
    import jax

    return np.asarray(jax.random.permutation(run.key("partition"), n))


def check(run, out) -> dict:
    fed_cfg, data = run.config["federation"], run.config["data"]
    Xtr, ytr, Xte, yte = out["data"]
    return compare.training(
        out["members"], Xtr, ytr, out["perm"], fed_cfg, data["n_classes"],
        run.traffic["reference_rounds"], count=out["count"], rounds_done=out["rounds_done"],
        f1_row=out["f1_row"], X_test=Xte, y_test=yte,
    )
