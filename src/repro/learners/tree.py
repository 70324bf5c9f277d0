"""Oblivious decision trees — the TPU-native analogue of the paper's
10-leaf SciKit-Learn tree.

An oblivious tree applies ONE (feature, threshold) test per level, shared
by all nodes of that level (CatBoost-style).  A depth-``d`` tree has
``2**d`` leaves and its fit/predict are dense fixed-shape tensor programs.
The fit is a staged pipeline with a precomputable data layer:

  bin        features are quantile-binned once per SHARD (not per round):
             ``learners/binning.py::BinnedDataset`` carries the edges and
             the digitized bin indices as the fit cache;
  histogram  each level accumulates a weighted class histogram
             C[leaf, feature, bin, class] — the compute hot-spot, routed
             through ``kernels/ops.py::tree_hist`` (the Pallas MXU kernel
             on the TPU in every fit path; elsewhere the segment-sum
             oracle, or the kernel interpreted under ``use_pallas``);
  select     split scores for every (feature, bin) candidate come from a
             reverse cumulative sum over the bin axis (split at bin b ==
             "x > edges[b]"); the best candidate maximises
             sum_leaf sum_side (sum_k c_k^2 / c_tot), which is equivalent
             to minimising weighted Gini impurity;
  leaf       leaf log-distributions from a weighted segment-sum.

``predict`` (``tree_predict_class``) selects each row's class from a
per-leaf class table; ``tree_predict_logits`` serves callers that want
the scores.

Every stage is expressed per-collaborator and vmaps cleanly;
``fit_tree_batched`` fuses the C collaborators of a federated round into
ONE histogram launch per level (the kernel folds the batch axis into its
grid).  Sample weights implement AdaBoost reweighting and padding masks.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.learners.base import LearnerSpec, WeakLearner, register, weighted_onehot
from repro.learners.binning import BinnedDataset, as_binned, bin_dataset, quantile_edges


class TreeParams(NamedTuple):
    feature: jax.Array  # [depth] i32   — feature tested at each level
    threshold: jax.Array  # [depth] f32 — raw threshold value
    leaf_logits: jax.Array  # [2**depth, K] f32 — log class distribution


def histogram(
    bin_idx: jax.Array,  # [n, d] i32 in [0, n_bins]
    leaf: jax.Array,  # [n] i32 in [0, n_leaves)
    wy: jax.Array,  # [n, K] weighted one-hot labels
    n_leaves: int,
    n_bins: int,
) -> jax.Array:
    """C[leaf, d, n_bins+1, K] — the segment-sum oracle formulation
    (kept as the public name; the fit path goes through the
    ``kernels/ops.py::tree_hist`` dispatch)."""
    return ref.tree_hist_ref(bin_idx, leaf, wy, n_leaves, n_bins + 1)


# ---------------------------------------------------------------------------
# Pipeline stages (each vmaps cleanly over a leading collaborator axis)
# ---------------------------------------------------------------------------


def _histogram_stage(
    bin_idx, leaf, wy, n_leaves: int, n_bins: int,
    *, use_pallas: bool = False, block_s: int | None = None, block_d: int | None = None,
):
    """Level histogram via the kernel dispatch: the MXU kernel on the
    TPU (whatever ``use_pallas`` says), the oracle or the interpreted
    kernel elsewhere.  Accepts single-fit ([n, d]) or batched
    ([C, n, d]) inputs — batched inputs run as ONE kernel launch (the
    batch axis folds into the Pallas grid).  Runs
    under ``jax.named_scope("hist")``: every fit path builds its level
    histograms here, so a profiler trace names their device time."""
    kw = {}
    if block_s is not None:
        kw["block_s"] = block_s
    if block_d is not None:
        kw["block_d"] = block_d
    with jax.named_scope("hist"):
        return ops.tree_hist(
            bin_idx, leaf, wy, n_leaves=n_leaves, n_bins_p1=n_bins + 1,
            use_pallas=use_pallas, **kw,
        )


def _split_scores(C: jax.Array) -> jax.Array:
    """Score every (feature, bin) split candidate.

    C: [L, d, B+1, K].  Splitting at bin b sends bins > b right.
    Returns [d, B] scores (higher = better): sum over leaves and sides of
    sum_k c_k^2 / c_tot  (maximising this minimises weighted Gini).
    """
    # right[:, :, b, :] = sum_{b' > b} C[..., b', :]
    totals = jnp.sum(C, axis=2, keepdims=True)  # [L, d, 1, K]
    right = totals - jnp.cumsum(C, axis=2)  # inclusive cumsum -> strictly greater
    right = right[:, :, :-1, :]  # candidates b in [0, B)
    left = totals - right  # [L, d, B, K]

    def purity(side):  # sum_k c_k^2 / c_tot, guarded for empty sides
        tot = jnp.sum(side, axis=-1)
        return jnp.where(tot > 0, jnp.sum(side * side, axis=-1) / jnp.maximum(tot, 1e-12), 0.0)

    return jnp.sum(purity(left) + purity(right), axis=0)  # [d, B]


def _select_stage(
    C: jax.Array,  # [L, d, B+1, K] level histogram
    edges: jax.Array,  # [d, B]
    key: jax.Array,
    level: int,
    n_bins: int,
    random_splits: bool,
    max_candidates: int,
):
    """Pick the level's (feature, bin) split.  Returns (f, b, threshold).

    ``random_splits`` scores only a random subset of candidates
    (ExtraTrees-style).  The level subkey is ``fold_in(key, level)`` —
    a pure function of (caller key, level), so the candidate subset at
    level L is deterministic and unchanged when ``depth`` changes (the
    old sequential split-chain re-derived every level key from the
    running carry, which made key consumption depend on loop structure).
    """
    scores = _split_scores(C)  # [d, B]
    if random_splits:
        sub = jax.random.fold_in(key, level)
        mask = jnp.zeros(scores.size, bool).at[
            jax.random.choice(sub, scores.size, (max_candidates,), replace=False)
        ].set(True).reshape(scores.shape)
        scores = jnp.where(mask, scores, -jnp.inf)
    flat = jnp.argmax(scores)
    f, b = flat // n_bins, flat % n_bins
    return f.astype(jnp.int32), b.astype(jnp.int32), edges[f, b]


def _descend_stage(bin_idx: jax.Array, leaf: jax.Array, f, b) -> jax.Array:
    """Advance every sample one level down the oblivious tree."""
    return leaf * 2 + (bin_idx[:, f] > b).astype(jnp.int32)


def _leaf_stage(wy: jax.Array, leaf: jax.Array, depth: int) -> jax.Array:
    """Leaf log class distributions from the final sample placement."""
    counts = jax.ops.segment_sum(wy, leaf, num_segments=2**depth)  # [leaves, K]
    tot = jnp.sum(counts, axis=-1, keepdims=True)
    # Empty leaves fall back to the global class prior.
    prior = jnp.sum(wy, axis=0) / jnp.maximum(jnp.sum(wy), 1e-12)
    dist = jnp.where(tot > 0, counts / jnp.maximum(tot, 1e-12), prior[None, :])
    return jnp.log(dist + 1e-12)


# ---------------------------------------------------------------------------
# Fit: single and collaborator-batched
# ---------------------------------------------------------------------------


def fit_tree(
    spec: LearnerSpec,
    params: TreeParams,
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    key: jax.Array,
    *,
    random_splits: bool = False,
    cache: BinnedDataset | jax.Array | None = None,
) -> TreeParams:
    """Fit one tree.  ``cache`` is the shard's fit precomputation — a
    ``BinnedDataset`` (edges + digitized bins, nothing X-dependent left
    to do), a bare ``[d, B]`` edges array (pre-binning cache format;
    digitizes here), or None (everything computed from ``X``)."""
    depth = spec.hp("depth", 4)
    n_bins = spec.hp("n_bins", 16)
    K = spec.n_classes
    max_cand = spec.hp("max_candidates", 8)
    del params  # trees are fit from scratch each round

    binned = as_binned(cache, X, n_bins)  # bin stage
    bin_idx, edges = binned.bin_idx, binned.edges
    wy = weighted_onehot(y, w, K)  # [n, K]

    leaf = jnp.zeros(X.shape[0], dtype=jnp.int32)
    feats, thrs = [], []
    for level in range(depth):
        C = _histogram_stage(bin_idx, leaf, wy, 2**level, n_bins)
        f, b, thr = _select_stage(C, edges, key, level, n_bins, random_splits, max_cand)
        feats.append(f)
        thrs.append(thr)
        leaf = _descend_stage(bin_idx, leaf, f, b)

    return TreeParams(
        feature=jnp.stack(feats),
        threshold=jnp.stack(thrs),
        leaf_logits=_leaf_stage(wy, leaf, depth),
    )


def fit_tree_batched(
    spec: LearnerSpec,
    X: jax.Array,  # [C, n, d]
    y: jax.Array,  # [C, n]
    w: jax.Array,  # [C, n]
    keys: jax.Array,  # [C, ...] per-collaborator keys
    cache: BinnedDataset | None = None,  # [C, ...]-batched BinnedDataset
    *,
    random_splits: bool = False,
    use_pallas: bool = False,
    block_s: int | None = None,
    block_d: int | None = None,
) -> TreeParams:
    """Fit all C collaborators' trees as ONE tensor program: per level,
    one ``tree_hist`` call builds every collaborator's histogram (one
    kernel launch on the TPU), and the select/descend/leaf stages vmap.

    On the oracle backends (``use_pallas=False`` off the TPU) this is
    bit-for-bit ``vmap(fit_tree)`` — the histogram oracle is the
    per-slice oracle vmapped, and every other stage is literally the
    single-fit stage under ``jax.vmap`` (regression-tested in
    tests/test_binning.py).
    """
    depth = spec.hp("depth", 4)
    n_bins = spec.hp("n_bins", 16)
    K = spec.n_classes
    max_cand = spec.hp("max_candidates", 8)

    if cache is None:
        cache = jax.vmap(lambda Xi: bin_dataset(Xi, n_bins))(X)
    elif not isinstance(cache, BinnedDataset):  # bare [C, d, B] edges
        cache = jax.vmap(lambda Xi, ei: as_binned(ei, Xi, n_bins))(X, cache)
    bin_idx, edges = cache.bin_idx, cache.edges  # [C, n, d], [C, d, B]
    wy = jax.vmap(lambda yi, wi: weighted_onehot(yi, wi, K))(y, w)  # [C, n, K]

    leaf = jnp.zeros(y.shape, dtype=jnp.int32)  # [C, n]
    feats, thrs = [], []
    for level in range(depth):
        C_hist = _histogram_stage(  # ONE launch for all C collaborators
            bin_idx, leaf, wy, 2**level, n_bins,
            use_pallas=use_pallas, block_s=block_s, block_d=block_d,
        )  # [C, L, d, B+1, K]
        f, b, thr = jax.vmap(
            lambda Ci, ei, ki: _select_stage(
                Ci, ei, ki, level, n_bins, random_splits, max_cand
            )
        )(C_hist, edges, keys)  # [C] each
        feats.append(f)
        thrs.append(thr)
        leaf = jax.vmap(_descend_stage)(bin_idx, leaf, f, b)

    return TreeParams(
        feature=jnp.stack(feats, axis=1),  # [C, depth]
        threshold=jnp.stack(thrs, axis=1),
        leaf_logits=jax.vmap(lambda wyi, li: _leaf_stage(wyi, li, depth))(wy, leaf),
    )


def init_tree(spec: LearnerSpec, key: jax.Array) -> TreeParams:
    depth = spec.hp("depth", 4)
    return TreeParams(
        feature=jnp.zeros((depth,), jnp.int32),
        threshold=jnp.zeros((depth,), jnp.float32),
        leaf_logits=jnp.zeros((2**depth, spec.n_classes), jnp.float32),
    )


def _leaf_index(params: TreeParams, X: jax.Array) -> jax.Array:
    """Every row's leaf: [n] i32 in [0, 2**depth)."""
    leaf = jnp.zeros(X.shape[0], dtype=jnp.int32)
    for level in range(params.feature.shape[0]):
        f = params.feature[level]
        bit = X[:, f] > params.threshold[level]
        leaf = leaf * 2 + bit.astype(jnp.int32)
    return leaf


def tree_predict_logits(spec: LearnerSpec, params: TreeParams, X: jax.Array) -> jax.Array:
    return params.leaf_logits[_leaf_index(params, X)]


def tree_predict_class(spec: LearnerSpec, params: TreeParams, X: jax.Array) -> jax.Array:
    """``WeakLearner.predict_class``: bit for bit
    ``argmax(tree_predict_logits(...), -1)``.  A leaf's argmax does not
    depend on the row, so it is taken once per leaf into a ``[2**depth]``
    class table, and each row picks its class with one elementwise select
    per leaf: no per-row gather of K logits, no ``[n, K]`` buffer, and
    under the scoring vmaps the selects fuse with the level compares."""
    ops.count_dispatch("tree_predict", "leaf_select")
    leaf = _leaf_index(params, X)
    cls = jnp.argmax(params.leaf_logits, axis=-1).astype(jnp.int32)  # [2**depth]
    out = jnp.broadcast_to(cls[0], leaf.shape)
    for l in range(1, cls.shape[0]):
        out = jnp.where(leaf == l, cls[l], out)
    return out


def tree_edges(spec: LearnerSpec, X: jax.Array) -> jax.Array:
    """The quantile bin edges alone — the pre-binning cache format, still
    accepted by ``fit_tree(cache=...)`` for back-compat."""
    return quantile_edges(X, spec.hp("n_bins", 16))


def tree_precompute(spec: LearnerSpec, X: jax.Array) -> BinnedDataset:
    """Shard-static fit precomputation (``WeakLearner.precompute``):
    quantile edges + digitized bin indices, so rounds never touch X."""
    return bin_dataset(X, spec.hp("n_bins", 16))


def _fit_tree_cached(spec, params, X, y, w, key, cache, *, random_splits=False):
    return fit_tree(
        spec, params, X, y, w, key, random_splits=random_splits, cache=cache
    )


decision_tree = register(
    WeakLearner(
        "decision_tree", init_tree, fit_tree, tree_predict_logits,
        precompute=tree_precompute, fit_cached=_fit_tree_cached,
        fit_batched=fit_tree_batched, predict_class=tree_predict_class,
    )
)

extra_tree = register(
    WeakLearner(
        "extra_tree",
        init_tree,
        functools.partial(fit_tree, random_splits=True),
        tree_predict_logits,
        precompute=tree_precompute,
        fit_cached=functools.partial(_fit_tree_cached, random_splits=True),
        fit_batched=functools.partial(fit_tree_batched, random_splits=True),
        predict_class=tree_predict_class,
    )
)
