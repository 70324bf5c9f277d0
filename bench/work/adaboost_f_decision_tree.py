"""Bytes and operations one AdaBoost.F round over depth-D oblivious trees
needs on each chip, whatever implements it.

Counted from the configuration's shapes: C collaborators of
``n = n_train // C`` rows, d features, K classes, depth D, B bins, the
collaborators spread evenly over the chips. Each input is read once per
use at its narrowest exact width: a bin index in the bytes that hold
B + 1 values, a leaf index in those that hold the level's leaves, a
label in those that hold K, a sample weight as the float32 it is.

- Fit, per level: every shard's bin indices, its rows' current leaf
  (not at level 0), label and weight; one add per (row, feature) into
  the level's histogram. The leaves: each row's final leaf, label and
  weight, one add per row.
- Score: every tree of the round on every local shard. A row's class
  under a tree needs its D tested bin indices (or all d, if fewer);
  one comparison per (tree, row, level). The weighted error of every
  tree reads each row's label and weight once; one add per (tree, row).
- Update: each row's weight, label and chosen-tree mistake are read and
  its weight written; two operations per row.

What is not counted: the split search over the histogram
(``2**level * d * (B + 1) * K`` per level, small against the rows), the
exchange between collaborators, and the eval.
"""
from __future__ import annotations

import math


def nbytes(values: int) -> int:
    """Whole bytes that hold ``values`` distinct values."""
    return max(1, math.ceil(math.log2(max(values, 2)) / 8))


def round_work(config: dict, chips: int) -> dict:
    fed, data = config["federation"], config["data"]
    C, d, K = fed["collaborators"], data["n_features"], data["n_classes"]
    D, B = fed["hparams"]["depth"], fed["hparams"]["n_bins"]
    n = data["n_train"] // C
    rows = n * C // chips  # rows held on one chip
    bin_b, label_b, w_b = nbytes(B + 1), nbytes(K), 4
    fit_bytes = sum(rows * (d * bin_b + (nbytes(2**lv) if lv else 0) + label_b + w_b)
                    for lv in range(D))
    fit_bytes += rows * (nbytes(2**D) + label_b + w_b)
    fit_ops = D * rows * d + rows
    score_bytes = rows * min(d, C * D) * bin_b + rows * (label_b + w_b)
    score_ops = C * rows * D + C * rows
    update_bytes = rows * (2 * w_b + label_b + 1)
    update_ops = 2 * rows
    return {"bytes": fit_bytes + score_bytes + update_bytes,
            "ops": fit_ops + score_ops + update_ops}
