"""Plain reference of AdaBoost.F over depth-``D`` oblivious decision trees.

Written from the algorithm's statement, in numpy, importing nothing of
the program:

- each collaborator bins its shard once: per feature, ``n_bins``
  quantile edges (linear interpolation at ``(1..n_bins)/(n_bins+1)``),
  and a cell's bin is the number of edges its value exceeds;
- a tree tests one (feature, threshold) per level, shared by all nodes
  of the level. Each level takes the split that maximises
  ``sum over leaves and sides of sum_k c_k^2 / c_tot`` over the weighted
  class histogram (least weighted Gini), the first maximum in
  (feature, bin) order; the threshold is that bin's edge. Leaves hold
  ``log(p + 1e-12)`` of the weighted class distribution, or the shard's
  weighted class prior where a leaf is empty. A tree predicts the first
  class of largest leaf value, descending by ``x > threshold``;
- a round fits one tree per collaborator on the globally normalised
  sample weights, scores every tree on every shard, takes the tree of
  least summed weighted error ``eps``, gives it
  ``alpha = clip(log((1 - e) / e) + log(K - 1), -10, 10)`` with
  ``e = clip(eps, 1e-10, 1 - 1e-10)``, and reweights
  ``w * exp(alpha * mistake)``, renormalised over all collaborators;
- the ensemble predicts the first class of largest alpha-weighted vote.

Two candidates can score exactly alike (at uniform weights, two splits
with the same class counts on each side do), and then any rounding
decides between them. So the reference can also ``follow`` a given run:
each round keeps that run's tree (found by the collaborator whose bin
edges hold its thresholds) and says how far each of its choices lies
below the reference's best, instead of asking for the same argmax.

``precision`` is ``"float64"`` for the reference, or ``"bfloat16"`` for
the control: inputs and every stored intermediate are rounded to
bfloat16, accumulations run wider, as a bfloat16 device path computes.

``fault`` plants one of the faults whose readings bound the limits:
``"half"`` leaves out the second half of every shard (the weights are
renormalised over the rest), ``"alter"`` appends the second-best tree
instead of the best, ``"no_exchange"`` leaves out the exchange between
collaborators (the error of every tree is taken on collaborator 0's
shard alone, and each shard's weights are renormalised on their own).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

TIE = 1e-9  # float64 scores or errors this close (relative) are tied


def rounder(precision: str) -> Callable[[np.ndarray], np.ndarray]:
    if precision == "float64":
        return lambda a: np.asarray(a, np.float64)
    if precision == "bfloat16":
        import ml_dtypes

        return lambda a: np.asarray(a, np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def quantile_edges(X: np.ndarray, n_bins: int) -> np.ndarray:
    qs = np.linspace(0.0, 1.0, n_bins + 2)[1:-1]
    return np.quantile(X, qs, axis=0).T  # [d, n_bins]


def digitize(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Number of edges each value exceeds. [n, d]."""
    return np.stack(
        [np.searchsorted(edges[j], X[:, j], side="left") for j in range(X.shape[1])], axis=1
    )


def _purity(side: np.ndarray) -> np.ndarray:
    tot = side.sum(-1)
    return np.where(tot > 0, (side * side).sum(-1) / np.maximum(tot, 1e-12), 0.0)


def fit_tree(bins, edges, y, w, *, depth: int, n_bins: int, K: int, rnd,
             follow=None) -> Dict[str, np.ndarray]:
    """One tree on a shard's bins ``[n, d]``, labels and weights.

    With ``follow`` (a (feature, bin) per level) the tree descends by
    those splits, and ``split_gap`` is the largest share by which one of
    them scores below the level's best. ``tied``: some level's best was
    tied with another candidate."""
    n, d = bins.shape
    nb1 = n_bins + 1
    w = rnd(w)
    # histogram cell of each (row, feature) at level 0: (feature, bin, class)
    cell = ((np.arange(d)[None, :] * nb1 + bins) * K + y[:, None]).ravel()
    weights = np.broadcast_to(w[:, None], (n, d)).ravel()
    leaf = np.zeros(n, np.int64)
    feats, thrs = [], []
    split_gap, tied = 0.0, False
    for level in range(depth):
        L = 2**level
        seg = (np.repeat(leaf, d) * (d * nb1 * K) + cell) if level else cell
        H = rnd(np.bincount(seg, weights=weights, minlength=L * d * nb1 * K)
                .reshape(L, d, nb1, K))
        totals = H.sum(axis=2, keepdims=True)
        right = rnd(totals - np.cumsum(H, axis=2))[:, :, :-1, :]
        left = rnd(totals - right)
        scores = rnd((rnd(_purity(left)) + rnd(_purity(right))).sum(axis=0)).ravel()  # [d*B]
        top = scores.max()
        tied |= bool(np.sum(scores >= top - TIE * abs(top)) > 1)
        if follow is None:
            f, b = divmod(int(np.argmax(scores)), n_bins)
        else:
            f, b = (int(v) for v in follow[level])
            split_gap = max(split_gap, float(top - scores[f * n_bins + b]) / max(abs(top), 1e-300))
        feats.append(f)
        thrs.append(edges[f, b])
        leaf = leaf * 2 + (bins[:, f] > b)
    n_leaves = 2**depth
    counts = rnd(np.bincount(leaf * K + y, weights=w, minlength=n_leaves * K).reshape(n_leaves, K))
    tot = counts.sum(-1, keepdims=True)
    wy = np.bincount(y, weights=w, minlength=K)
    prior = wy / max(wy.sum(), 1e-12)
    dist = np.where(tot > 0, counts / np.maximum(tot, 1e-12), prior[None, :])
    return {
        "feature": np.asarray(feats, np.int64),
        "threshold": np.asarray(thrs, np.float64),
        "leaf_logits": rnd(np.log(rnd(dist) + 1e-12)),
        "split_gap": split_gap,
        "tied": tied,
    }


def locate(features, thresholds, edges: List[np.ndarray]):
    """Where a tree's thresholds come from: (collaborator, [(feature,
    bin)] per level, the largest distance of a threshold from that
    edge as a share of the edge or the collaborator's median edge,
    whichever is larger). None where a feature is out of range."""
    features = np.asarray(features, np.int64)
    thresholds = np.asarray(thresholds, np.float64)
    d = edges[0].shape[0]
    if np.any((features < 0) | (features >= d)) or not np.all(np.isfinite(thresholds)):
        return None
    best = None
    levels = np.arange(len(features))
    for c, e in enumerate(edges):
        rows = e[features]  # [D, B]
        b = np.argmin(np.abs(rows - thresholds[:, None]), axis=1)
        got = rows[levels, b]
        scale = np.maximum(np.abs(got), max(float(np.median(np.abs(e))), 1e-30))
        dist = float(np.max(np.abs(got - thresholds) / scale))
        if best is None or dist < best[2]:
            best = (c, [(int(f), int(bb)) for f, bb in zip(features, b)], dist)
    return best


def predict(tree: Dict[str, np.ndarray], X: np.ndarray, *, features_first: bool = False) -> np.ndarray:
    """Class predictions of one tree on rows ``X [..., n, d]`` (or on
    ``X [d, ..., n]`` with ``features_first``)."""
    col = (lambda f: X[f]) if features_first else (lambda f: X[..., f])
    leaf = None
    for f, t in zip(tree["feature"], tree["threshold"]):
        bit = (col(f) > t).astype(np.uint8)
        leaf = bit if leaf is None else leaf * np.uint8(2) + bit
    return np.argmax(tree["leaf_logits"], axis=-1)[leaf]


def _alpha(eps: float, K: int, rnd) -> float:
    e = min(max(float(eps), 1e-10), 1.0 - 1e-10)
    return float(rnd(np.clip(np.log((1.0 - e) / e) + np.log(K - 1.0), -10.0, 10.0)))


def adaboost_f(Xs, ys, masks, *, rounds: int, depth: int, n_bins: int, K: int,
               precision: str = "float64", fault: Optional[str] = None,
               follow: Optional[Dict[str, np.ndarray]] = None) -> List[Dict[str, np.ndarray]]:
    """``rounds`` rounds of AdaBoost.F over collaborator shards
    ``Xs [C, n, d]``, ``ys [C, n]``, ``masks [C, n]``. Returns one dict
    per round: the round's tree (``feature``, ``threshold``,
    ``leaf_logits``), its ``alpha`` and its collaborator ``chosen``.

    With ``follow`` (a run's members: ``feature``, ``threshold`` of
    ``[rounds, D]``) each round keeps that run's tree instead of its own
    choice, refits its leaves, and adds ``split_gap`` (see
    :func:`fit_tree`), ``threshold_gap`` (see :func:`locate`) and
    ``choice_gap``: by what share the tree's weighted error exceeds the
    least of the collaborators' own trees, leaving out those whose fit
    met a tie. A tree whose thresholds cannot be placed reads ``inf``."""
    rnd = rounder(precision)
    Xs = rnd(Xs)
    masks = np.asarray(masks, np.float64)
    if fault == "half":
        masks = masks.copy()
        masks[:, masks.shape[1] // 2:] = 0.0
    C = Xs.shape[0]
    edges = [rnd(quantile_edges(Xs[c], n_bins)) for c in range(C)]
    bins = [digitize(Xs[c], edges[c]) for c in range(C)]
    w = rnd(masks / max(masks.sum(), 1.0))
    XT = np.ascontiguousarray(np.moveaxis(Xs, -1, 0))  # [d, C, n]
    kw = dict(depth=depth, n_bins=n_bins, K=K, rnd=rnd)
    out = []
    for r in range(rounds):
        trees = [fit_tree(bins[c], edges[c], ys[c], w[c], **kw) for c in range(C)]
        preds = np.stack([predict(t, XT, features_first=True) for t in trees])  # [h, i, n]
        wrong = preds != ys[None]
        errs = rnd((wrong * w[None]).sum(axis=-1))  # [h, i]
        if fault == "no_exchange":
            eps = rnd(errs[:, 0] / max(w[0].sum(), 1e-30))
        else:
            eps = rnd(errs.sum(axis=1))  # [h]
        gaps = {}
        if follow is None:
            order = np.argsort(eps, kind="stable")
            c = int(order[1] if fault == "alter" else order[0])
            tree, mistakes, e = trees[c], wrong[c], eps[c]
        else:
            found = locate(follow["feature"][r], follow["threshold"][r], edges)
            if found is None:
                out.append({"split_gap": np.inf, "threshold_gap": np.inf, "choice_gap": np.inf})
                break
            c, splits, gaps["threshold_gap"] = found
            tree = fit_tree(bins[c], edges[c], ys[c], w[c], follow=splits, **kw)
            mistakes = predict(tree, XT, features_first=True) != ys
            e = float(rnd((mistakes * w).sum()))
            clean = [float(eps[h]) for h in range(C) if not trees[h]["tied"]]
            least = min(clean) if clean else e
            gaps["split_gap"] = tree["split_gap"]
            gaps["choice_gap"] = max(0.0, e - least) / max(least, 1e-30)
        alpha = _alpha(e, K, rnd)
        w = rnd(w * np.exp(alpha * mistakes) * masks)
        if fault == "no_exchange":
            w = rnd(w / np.maximum(w.sum(axis=1, keepdims=True), 1e-30) / C)
        else:
            w = rnd(w / max(w.sum(), 1e-30))
        out.append({"feature": tree["feature"], "threshold": tree["threshold"],
                    "leaf_logits": tree["leaf_logits"], "alpha": alpha, "chosen": c, **gaps})
    return out


def ensemble_predict(features, thresholds, leaf_logits, alphas, X, K: int,
                     rnd=None) -> np.ndarray:
    """Alpha-weighted vote of the members ``[T, ...]`` on rows ``X [n, d]``."""
    rnd = rnd or rounder("float64")
    votes = np.zeros((X.shape[0], K))
    for f, t, l, a in zip(features, thresholds, leaf_logits, alphas):
        pred = predict({"feature": f, "threshold": t, "leaf_logits": l}, X)
        votes[np.arange(X.shape[0]), pred] += float(a)
        votes = rnd(votes)
    return votes


def f1_macro(y_true: np.ndarray, y_pred: np.ndarray, K: int) -> float:
    """Macro F1 over the classes present in ``y_true``."""
    cm = np.zeros((K, K))
    np.add.at(cm, (y_true, y_pred), 1.0)
    tp = np.diag(cm)
    fp, fn = cm.sum(0) - tp, cm.sum(1) - tp
    precision = tp / np.maximum(tp + fp, 1e-12)
    recall = tp / np.maximum(tp + fn, 1e-12)
    f1 = 2 * precision * recall / np.maximum(precision + recall, 1e-12)
    present = cm.sum(1) > 0
    return float(f1[present].sum() / max(present.sum(), 1))
