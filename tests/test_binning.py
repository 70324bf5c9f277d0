"""BinnedDataset fit cache + the batched kernel-backed tree-fit pipeline:
cache forms are interchangeable, batched fits are bit-for-bit with C
independent fits, and fused rounds are identical with the pipeline on or
off (the multi-layer-refactor acceptance regression)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boosting
from repro.core.plan import OptimizationFlags
from repro.learners import LearnerSpec, get_learner
from repro.learners.binning import BinnedDataset, as_binned, bin_dataset, digitize, quantile_edges
from repro.learners.tree import fit_tree, fit_tree_batched

HPARAMS = {
    "decision_tree": {"depth": 3, "n_bins": 8},
    "extra_tree": {"depth": 3, "n_bins": 8, "max_candidates": 10},
}


def _blobs(key, n=240, d=5, K=3, sep=3.0):
    kc, kx, ky = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (K, d)) * sep
    y = jax.random.randint(ky, (n,), 0, K)
    X = centers[y] + jax.random.normal(kx, (n, d))
    return X, y


def _shards(key, C=3, n=120, d=5, K=3):
    X, y = _blobs(key, n=C * n, d=d, K=K)
    Xs = X.reshape(C, n, d)
    ys = y.reshape(C, n)
    ws = jnp.ones(ys.shape, jnp.float32)
    return Xs, ys, ws


# ---------------------------------------------------------------------------
# Data layer
# ---------------------------------------------------------------------------


def test_bin_dataset_composes_the_stages():
    X, _ = _blobs(jax.random.PRNGKey(0))
    binned = bin_dataset(X, 8)
    np.testing.assert_array_equal(
        np.asarray(binned.edges), np.asarray(quantile_edges(X, 8))
    )
    np.testing.assert_array_equal(
        np.asarray(binned.bin_idx), np.asarray(digitize(X, binned.edges))
    )
    assert binned.n_bins == 8
    assert binned.bin_idx.dtype == jnp.int32
    assert int(binned.bin_idx.max()) <= 8 and int(binned.bin_idx.min()) >= 0


def test_as_binned_accepts_every_cache_form():
    """None, bare edges (pre-binning cache format) and the full
    BinnedDataset must coerce to the same cache."""
    X, _ = _blobs(jax.random.PRNGKey(1))
    full = bin_dataset(X, 8)
    for cache in (None, full.edges, full):
        got = as_binned(cache, X, 8)
        assert isinstance(got, BinnedDataset)
        np.testing.assert_array_equal(np.asarray(got.edges), np.asarray(full.edges))
        np.testing.assert_array_equal(np.asarray(got.bin_idx), np.asarray(full.bin_idx))


def test_boost_state_carries_binned_cache():
    Xs, ys, ws = _shards(jax.random.PRNGKey(2))
    learner = get_learner("decision_tree")
    spec = LearnerSpec("decision_tree", Xs.shape[-1], 3, HPARAMS["decision_tree"])
    state = boosting.init_boost_state(learner, spec, 4, ws, jax.random.PRNGKey(3), X=Xs)
    assert isinstance(state.fit_cache, BinnedDataset)
    assert state.fit_cache.bin_idx.shape == Xs.shape  # [C, n, d]
    assert state.fit_cache.edges.shape == (Xs.shape[0], Xs.shape[-1], 8)


# ---------------------------------------------------------------------------
# Builder layer: cached == uncached, batched == vmapped (bit-for-bit)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(HPARAMS))
def test_fit_cached_is_bitforbit_with_fit(name):
    key = jax.random.PRNGKey(4)
    X, y = _blobs(key)
    spec = LearnerSpec(name, X.shape[1], 3, HPARAMS[name])
    learner = get_learner(name)
    w = jax.random.uniform(jax.random.PRNGKey(5), y.shape)
    plain = learner.fit(spec, None, X, y, w, key)
    cached = learner.fit_cached(spec, None, X, y, w, key, learner.precompute(spec, X))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(cached)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bare_edges_cache_backcompat():
    """The pre-binning cache format (a bare edges array, produced by
    ``tree_edges``) must keep working in both the single and the
    batched fit — including on a round's default batched path."""
    from repro.learners.tree import tree_edges

    key = jax.random.PRNGKey(6)
    X, y = _blobs(key)
    spec = LearnerSpec("decision_tree", X.shape[1], 3, HPARAMS["decision_tree"])
    w = jnp.ones(y.shape, jnp.float32)
    edges = tree_edges(spec, X)
    np.testing.assert_array_equal(np.asarray(edges), np.asarray(quantile_edges(X, 8)))
    via_edges = fit_tree(spec, None, X, y, w, key, cache=edges)
    plain = fit_tree(spec, None, X, y, w, key)
    for a, b in zip(jax.tree.leaves(via_edges), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # batched fit + a legacy bare-edges BoostState cache (e.g. restored
    # from a PR-2-era run) must coerce, not crash, on the default path
    Xs, ys, ws = _shards(key)
    learner = get_learner("decision_tree")
    full = boosting.init_boost_state(learner, spec, 2, ws, jax.random.PRNGKey(7), X=Xs)
    legacy = boosting.BoostState(full.ensemble, full.weights, full.key, full.fit_cache.edges)
    s_legacy, m_legacy = boosting.adaboost_f_round(learner, spec, legacy, Xs, ys, ws)
    s_full, m_full = boosting.adaboost_f_round(learner, spec, full, Xs, ys, ws)
    assert int(m_legacy["chosen"]) == int(m_full["chosen"])
    np.testing.assert_array_equal(
        np.asarray(s_legacy.weights), np.asarray(s_full.weights)
    )


@pytest.mark.parametrize("name", sorted(HPARAMS))
def test_fit_batched_equals_vmapped_singles(name):
    """ONE batched tensor program == C independent fits, bit-for-bit
    (the oracle-path acceptance criterion of the pipeline refactor)."""
    key = jax.random.PRNGKey(7)
    Xs, ys, ws = _shards(key)
    spec = LearnerSpec(name, Xs.shape[-1], 3, HPARAMS[name])
    learner = get_learner(name)
    keys = jax.random.split(jax.random.PRNGKey(8), Xs.shape[0])
    cache = jax.vmap(lambda Xi: learner.precompute(spec, Xi))(Xs)
    batched = learner.fit_batched(spec, Xs, ys, ws, keys, cache)
    singles = jax.vmap(
        lambda Xi, yi, wi, ki, ci: learner.fit_cached(spec, None, Xi, yi, wi, ki, ci)
    )(Xs, ys, ws, keys, cache)
    for a, b in zip(jax.tree.leaves(batched), jax.tree.leaves(singles)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fit_batched_without_cache_builds_one():
    key = jax.random.PRNGKey(9)
    Xs, ys, ws = _shards(key)
    spec = LearnerSpec("decision_tree", Xs.shape[-1], 3, HPARAMS["decision_tree"])
    keys = jax.random.split(key, Xs.shape[0])
    learner = get_learner("decision_tree")
    cache = jax.vmap(lambda Xi: learner.precompute(spec, Xi))(Xs)
    a = fit_tree_batched(spec, Xs, ys, ws, keys)
    b = fit_tree_batched(spec, Xs, ys, ws, keys, cache)
    for x, yv in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(yv))


def test_fit_batched_pallas_matches_oracle():
    """Kernel-backed histogram stage (interpret mode on CPU) vs the
    segment-sum oracle, including non-default block tiling."""
    key = jax.random.PRNGKey(10)
    Xs, ys, ws = _shards(key, C=2, n=96)
    spec = LearnerSpec("decision_tree", Xs.shape[-1], 3, HPARAMS["decision_tree"])
    keys = jax.random.split(key, Xs.shape[0])
    oracle = fit_tree_batched(spec, Xs, ys, ws, keys)
    kernel = fit_tree_batched(
        spec, Xs, ys, ws, keys, use_pallas=True, block_s=32, block_d=4
    )
    np.testing.assert_array_equal(
        np.asarray(oracle.feature), np.asarray(kernel.feature)
    )
    np.testing.assert_allclose(
        np.asarray(oracle.threshold), np.asarray(kernel.threshold), atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(oracle.leaf_logits), np.asarray(kernel.leaf_logits), atol=1e-4
    )


def test_extra_tree_level_keys_stable_across_depth():
    """The random-split subset at level L is a pure function of
    (caller key, L): growing the tree must not reshuffle the candidate
    subsets of the levels that already existed."""
    key = jax.random.PRNGKey(11)
    X, y = _blobs(key)
    w = jnp.ones(y.shape, jnp.float32)
    learner = get_learner("extra_tree")
    shallow_spec = LearnerSpec("extra_tree", X.shape[1], 3,
                               {"depth": 2, "n_bins": 8, "max_candidates": 10})
    deep_spec = LearnerSpec("extra_tree", X.shape[1], 3,
                            {"depth": 4, "n_bins": 8, "max_candidates": 10})
    shallow = learner.fit(shallow_spec, None, X, y, w, key)
    deep = learner.fit(deep_spec, None, X, y, w, key)
    np.testing.assert_array_equal(
        np.asarray(deep.feature[:2]), np.asarray(shallow.feature)
    )
    np.testing.assert_array_equal(
        np.asarray(deep.threshold[:2]), np.asarray(shallow.threshold)
    )


# ---------------------------------------------------------------------------
# Round level: the refactored pipeline must not change the federation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("alg", ["adaboost_f", "distboost_f", "bagging"])
def test_fused_round_batched_fit_bitforbit(alg):
    """Acceptance regression: fused rounds with the batched pipeline on
    vs off (use_pallas=False both) are bit-for-bit identical."""
    key = jax.random.PRNGKey(12)
    Xs, ys, ws = _shards(key)
    spec = LearnerSpec("decision_tree", Xs.shape[-1], 3, HPARAMS["decision_tree"])
    learner = get_learner("decision_tree")
    committee = Xs.shape[0] if alg == "distboost_f" else None
    mk = lambda: boosting.init_boost_state(
        learner, spec, 3, ws, jax.random.PRNGKey(13), committee_size=committee, X=Xs
    )
    s_batched, s_loop = mk(), mk()
    rfn = boosting.ROUND_FNS[alg]
    f_batched = jax.jit(lambda s: rfn(learner, spec, s, Xs, ys, ws, batched_fit=True))
    f_loop = jax.jit(lambda s: rfn(learner, spec, s, Xs, ys, ws, batched_fit=False))
    for _ in range(3):
        s_batched, m_b = f_batched(s_batched)
        s_loop, m_l = f_loop(s_loop)
        assert int(m_b["chosen"]) == int(m_l["chosen"])
    np.testing.assert_array_equal(
        np.asarray(s_batched.weights), np.asarray(s_loop.weights)
    )
    np.testing.assert_array_equal(
        np.asarray(s_batched.ensemble.alpha), np.asarray(s_loop.ensemble.alpha)
    )
    for a, b in zip(
        jax.tree.leaves(s_batched.ensemble.params), jax.tree.leaves(s_loop.ensemble.params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_local_fits_dispatches_to_fit_batched():
    """With a cache present the fused fit path must take the batched
    route (and fall back to vmap(fit_cached) when batching is off)."""
    key = jax.random.PRNGKey(14)
    Xs, ys, ws = _shards(key)
    spec = LearnerSpec("decision_tree", Xs.shape[-1], 3, HPARAMS["decision_tree"])
    learner = get_learner("decision_tree")
    calls = {"batched": 0, "cached": 0}
    base_batched, base_cached = learner.fit_batched, learner.fit_cached

    def counting_batched(*a, **kw):
        calls["batched"] += 1
        return base_batched(*a, **kw)

    def counting_cached(*a, **kw):
        calls["cached"] += 1
        return base_cached(*a, **kw)

    counted = dataclasses.replace(
        learner, fit_batched=counting_batched, fit_cached=counting_cached
    )
    cache = jax.vmap(lambda Xi: learner.precompute(spec, Xi))(Xs)
    boosting._local_fits(counted, spec, ws, Xs, ys, key, cache, batched=True)
    assert calls == {"batched": 1, "cached": 0}
    boosting._local_fits(counted, spec, ws, Xs, ys, key, cache, batched=False)
    assert calls["batched"] == 1 and calls["cached"] >= 1  # vmap traces once


def test_optimization_flags_expose_tree_tiling():
    flags = OptimizationFlags()
    assert flags.batched_fit is True
    # tiles follow the shape unless set (kernels/tree_hist.tiles)
    assert flags.tree_block_s is None and flags.tree_block_d is None
    flags = dataclasses.replace(flags, tree_block_s=512, tree_block_d=8)
    # a round accepts the tiling knobs on the oracle path (no-ops there)
    key = jax.random.PRNGKey(15)
    Xs, ys, ws = _shards(key)
    spec = LearnerSpec("decision_tree", Xs.shape[-1], 3, HPARAMS["decision_tree"])
    learner = get_learner("decision_tree")
    state = boosting.init_boost_state(learner, spec, 1, ws, key, X=Xs)
    s_a, _ = boosting.adaboost_f_round(
        learner, spec, state, Xs, ys, ws,
        block_s=flags.tree_block_s, block_d=flags.tree_block_d,
    )
    s_b, _ = boosting.adaboost_f_round(learner, spec, state, Xs, ys, ws)
    np.testing.assert_array_equal(np.asarray(s_a.weights), np.asarray(s_b.weights))
