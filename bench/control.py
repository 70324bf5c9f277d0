"""Readings that bound the limits of ``correct``: the control and the
planted faults, at a cell's own size, on the chip's data.

  python3 bench/control.py --workload adult_c8.train --seeds 1 2 3

For each seed this builds the cell's data and partition as a run does
and puts in the program's place:

- ``control``: the plain reference computed in bfloat16, the precision
  below the configuration's float32 (for serving: the vote tally of the
  served ensemble in bfloat16);
- the faults a cell can have, planted in the reference: ``half`` (the
  second half of every shard left out), ``alter`` (a wrong answer: the
  second-best tree appended; for serving, every answer flipped to
  another class), ``no_exchange`` on cells over several chips (each
  collaborator's errors kept to itself); for serving, ``half`` leaves
  half of the requests unanswered. A state left unchanged reads
  ``count_gap`` = the rounds run and needs no run here;
- ``eval_skip``, on cells that evaluate: the whole cell run with a fault
  planted in the program's eval, whose tally leaves out the newest
  member of every fold (a window of ``--seconds``).

Each is compared with the reference as a run's outputs are
(``bench/compare.py``); one JSON line per seed. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import compare  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.reference import adaboost_f_decision_tree as ref  # noqa: E402


def control_f1_row(members: dict, X_test: np.ndarray, y_test: np.ndarray, K: int) -> dict:
    """The control's eval row: F1 of its members' vote tallied in bfloat16."""
    bf16 = ref.rounder("bfloat16")
    m = members
    votes = ref.ensemble_predict(m["feature"], bf16(m["threshold"]), bf16(m["leaf_logits"]),
                                 bf16(m["alpha"]), bf16(X_test), K, rnd=bf16)
    return {"round": len(m["alpha"]) - 1, "f1": ref.f1_macro(y_test, np.argmax(votes, -1), K)}


def training(run, faults) -> dict:
    from bench.drivers import fused

    fed, data = run.config["federation"], run.config["data"]
    K, R = data["n_classes"], run.traffic["reference_rounds"]
    Xtr, ytr, Xte, yte = (np.asarray(a) for a in run.data())
    perm = fused.partition_perm(run, Xtr.shape[0])
    evaluates = "eval_every" in run.traffic
    out = {}
    for name in ["control", *faults]:
        got = compare.reference_members(
            Xtr, ytr, perm, fed, K, R,
            precision="bfloat16" if name == "control" else "float64",
            fault=None if name == "control" else name,
        )
        f1_row = control_f1_row(got, Xte, yte, K) if name == "control" and evaluates else None
        out[name] = compare.training(got, Xtr, ytr, perm, fed, K, R, count=R, rounds_done=R,
                                     f1_row=f1_row, X_test=Xte, y_test=yte)
    return out


def eval_skip(benchmark, cell, seed: int, seconds: float, devices) -> dict:
    """The cell's checks with the eval's tally leaving out the newest
    member of every fold."""
    from repro.core import scoring

    real = scoring.tally_new_votes

    def skipping(learner, spec, ensemble, tally, X, **kw):
        out = real(learner, spec, ensemble._replace(count=ensemble.count - 1), tally, X, **kw)
        return out._replace(counted=ensemble.count)

    scoring.tally_new_votes = skipping
    try:
        res = bench_run.run_cell(benchmark, cell, seed=seed, seconds=seconds, trace=False,
                                 devices=devices)
    finally:
        scoring.tally_new_votes = real
    return {k: v["value"] for k, v in res["checks"].items()}


def serving(run) -> dict:
    from bench.drivers import serve

    _, members, count, X = serve.setup_engine(run)
    out_ref = {"members": members, "count": count, "X_test": X}
    votes, mass = serve.reference_votes(out_ref)
    due, rows = serve.schedule(float(run.traffic["rate_rows_per_s"]), run.seconds,
                               X.shape[0], run.seed)
    bf16 = ref.rounder("bfloat16")
    m = members
    ctl = ref.ensemble_predict(m["feature"][:count], bf16(m["threshold"][:count]),
                               bf16(m["leaf_logits"][:count]), bf16(m["alpha"][:count]),
                               bf16(X), votes.shape[1], rnd=bf16)
    ok = np.ones(len(rows), bool)
    right = np.argmax(votes[rows], -1)
    half = ok.copy()
    half[len(rows) // 2:] = False
    return {
        "control": compare.serving(votes, rows, np.argmax(ctl[rows], -1), ok, mass),
        "alter": compare.serving(votes, rows, (right + 1) % votes.shape[1], ok, mass),
        "half": compare.serving(votes, rows, right, half, mass),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="window of the serving control and of the eval fault's runs")
    ap.add_argument("--no-faults", action="store_true",
                    help="training cells: read the control alone")
    args = ap.parse_args(argv)
    benchmark = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    cell = bench_run.find_cell(args.workload, benchmark)
    try:
        devices = bench_run.require_chips(cell["chips"])
    except bench_run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    bench_run.use_compile_cache()
    config = bench_run.load_json(bench_run.BENCH / "configs" / f"{cell['config']}.json")
    traffic = bench_run.load_json(bench_run.BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = bench_run.load_json(bench_run.BENCH / "limits" / f"{cell['name']}.json")
    for seed in args.seeds:
        run = bench_run.Run(cell, config, traffic, seed=seed, seconds=args.seconds,
                            trace=False, compiles=None)
        if traffic["driver"] == "serve":
            out = serving(run)
        else:
            faults = ["half", "alter"] + (["no_exchange"] if cell["chips"] > 1 else [])
            out = training(run, [] if args.no_faults else faults)
            if "f1_gap" in limits and not args.no_faults:
                out["eval_skip"] = eval_skip(benchmark, cell, seed, args.seconds, devices)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
