"""The numbers that decide ``correct``: what a timed path produced,
held against the plain reference (``bench/reference/``).

Training (``training``): the reference follows the federation's first
rounds from the same data and partition, keeping the tree the program
stored for each round; each split, threshold, choice of tree, leaf and
alpha is held against the reference's own best and its arithmetic.
``count_gap`` holds the members stored against the rounds run over the
whole window. The eval is checked at the window's last eval row: the F1
the program reported against the F1 of the reference's vote of the
program's own members up to that row.

Serving (``serving``): every request due in the window is answered, and
each answer is the class of largest vote in the reference's tally of
the served ensemble (``vote_gap``: how far below the best vote the
served class lies, as a share of the summed alphas).
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import numpy as np

from bench.reference import adaboost_f_decision_tree as ref


def _rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |got - want| against |want| or the median |want|, whichever
    is larger (a value near zero would make any rounding look large)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), max(float(np.median(np.abs(want))), 1e-30))
    return float(np.max(np.abs(got - want) / scale))


MEMBER_KEYS = ("feature", "threshold", "leaf_logits", "alpha")


def iid_shards(X: np.ndarray, y: np.ndarray, C: int, perm: np.ndarray):
    """The configuration's iid partition: rows in the order of the
    partition key's permutation, cut into C equal shards, remainder dropped."""
    per = X.shape[0] // C
    idx = perm[: per * C]
    return X[idx].reshape(C, per, -1), y[idx].reshape(C, per), np.ones((C, per))


def reference_members(X, y, perm, fed: dict, K: int, rounds: int,
                      precision: str = "float64", fault: Optional[str] = None) -> dict:
    """The reference's own first ``rounds`` members as stacked arrays
    (the control, or a planted fault, in the program's place)."""
    Xs, ys, ms = iid_shards(X, y, fed["collaborators"], perm)
    hp = fed["hparams"]
    out = ref.adaboost_f(Xs, ys, ms, rounds=rounds, depth=hp["depth"], n_bins=hp["n_bins"],
                         K=K, precision=precision, fault=fault)
    return {k: np.stack([o[k] for o in out]) for k in MEMBER_KEYS}


def training(members: dict, X: np.ndarray, y: np.ndarray, perm: np.ndarray, fed: dict,
             K: int, rounds: int, *, count: int, rounds_done: int, f1_row: Optional[dict],
             X_test: np.ndarray, y_test: np.ndarray) -> Dict[str, float]:
    """``members``: the program's ensemble slots (``feature``,
    ``threshold``, ``leaf_logits``, ``alpha``); ``X``, ``y``, ``perm``:
    its training rows and partition order; ``rounds``: how many of its
    first rounds the reference follows; ``count``: members the program
    stored; ``rounds_done``: rounds it ran; ``f1_row``: its last eval row
    (``round``, ``f1``), if the cell evaluates."""
    got = {k: np.asarray(members[k])[:rounds] for k in MEMBER_KEYS}
    Xs, ys, ms = iid_shards(X, y, fed["collaborators"], perm)
    hp = fed["hparams"]
    out = ref.adaboost_f(Xs, ys, ms, rounds=rounds, depth=hp["depth"], n_bins=hp["n_bins"],
                         K=K, follow=got)
    placed = len(out) == rounds and "alpha" in out[-1]
    checks = {
        "count_gap": float(abs(int(count) - int(rounds_done))),
        "split_gap": max(o["split_gap"] for o in out),
        "threshold_gap": max(o["threshold_gap"] for o in out),
        "choice_gap": max(o["choice_gap"] for o in out),
        "leaf_gap": float(np.max(np.abs(got["leaf_logits"] - np.stack([o["leaf_logits"] for o in out]))))
        if placed else math.inf,
        "alpha_gap": _rel_gap(got["alpha"], np.array([o["alpha"] for o in out]))
        if placed else math.inf,
    }
    if f1_row is not None:
        T = int(f1_row["round"]) + 1
        votes = ref.ensemble_predict(
            np.asarray(members["feature"])[:T], np.asarray(members["threshold"])[:T],
            np.asarray(members["leaf_logits"])[:T], np.asarray(members["alpha"])[:T],
            X_test, K,
        )
        f1 = ref.f1_macro(y_test, np.argmax(votes, axis=-1), K)
        checks["f1_gap"] = abs(float(f1_row["f1"]) - f1)
    return checks


def vote_gaps(votes: np.ndarray, answers: np.ndarray, alpha_mass: float) -> np.ndarray:
    """Per answer: how far the served class's vote lies below the best
    vote, as a share of the summed |alpha| (0 where it is the best)."""
    best = votes.max(axis=-1)
    got = votes[np.arange(votes.shape[0]), answers]
    return (best - got) / max(alpha_mass, 1e-30)


def serving(votes_by_row: np.ndarray, rows: np.ndarray, answers: np.ndarray,
            answered: np.ndarray, alpha_mass: float) -> Dict[str, float]:
    """``votes_by_row``: the reference's tally of each test row; ``rows``:
    the row each request sent; ``answers``: what it was told (any value
    where not ``answered``)."""
    ok = answered.astype(bool)
    valid = ok & (answers >= 0) & (answers < votes_by_row.shape[1])
    gaps = vote_gaps(votes_by_row[rows[valid]], answers[valid].astype(np.int64), alpha_mass)
    return {
        "unanswered": float(np.sum(~valid)),
        "vote_gap": float(gaps.max()) if gaps.size else 0.0,
    }


def finite(checks: Dict[str, float]) -> Dict[str, Optional[float]]:
    """JSON-safe values: a number that is not finite becomes None (and fails)."""
    return {k: (v if isinstance(v, (int, float)) and math.isfinite(v) else None)
            for k, v in checks.items()}
