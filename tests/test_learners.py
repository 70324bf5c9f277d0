"""Every weak-learner family: fits jit-compiled, beats chance on separable
data, and respects sample weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.metrics import accuracy
from repro.learners import LearnerSpec, available_learners, get_learner

HPARAMS = {
    "decision_tree": {"depth": 4, "n_bins": 16},
    "extra_tree": {"depth": 4, "n_bins": 16, "max_candidates": 16},
    "ridge": {"l2": 1.0},
    "mlp": {"hidden": 32, "steps": 100, "lr": 0.05},
    "gaussian_nb": {},
    "nearest_centroid": {},
}


def _blobs(key, n=400, d=6, K=3, sep=3.0):
    kc, kx, ky = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (K, d)) * sep
    y = jax.random.randint(ky, (n,), 0, K)
    X = centers[y] + jax.random.normal(kx, (n, d))
    return X, y


def test_all_six_families_registered():
    assert set(HPARAMS) <= set(available_learners())


@pytest.mark.parametrize("name", sorted(HPARAMS))
def test_beats_chance(name):
    key = jax.random.PRNGKey(0)
    X, y = _blobs(key)
    spec = LearnerSpec(name, X.shape[1], 3, HPARAMS[name])
    learner = get_learner(name)
    w = jnp.ones(y.shape, jnp.float32)
    params = jax.jit(lambda X, y, w: learner.fit(spec, None, X, y, w, key))(X, y, w)
    acc = float(accuracy(y, learner.predict(spec, params, X)))
    assert acc > 0.7, (name, acc)


@pytest.mark.parametrize("name", sorted(HPARAMS))
def test_weights_matter(name):
    """Zero-weighting class 2 must push predictions toward classes 0/1."""
    key = jax.random.PRNGKey(1)
    X, y = _blobs(key, sep=2.0)
    spec = LearnerSpec(name, X.shape[1], 3, HPARAMS[name])
    learner = get_learner(name)
    w = jnp.where(y == 2, 0.0, 1.0)
    params = learner.fit(spec, None, X, y, w, key)
    pred = learner.predict(spec, params, X)
    # on the classes it WAS trained on, class 2 must (almost) never win
    trained = y != 2
    frac2 = float(jnp.sum(((pred == 2) & trained).astype(jnp.float32))
                  / jnp.sum(trained.astype(jnp.float32)))
    assert frac2 < 0.1, (name, frac2)


@pytest.mark.parametrize("name", sorted(HPARAMS))
def test_vmap_across_collaborators(name):
    """vmap(fit) is the basis of the fused federated round."""
    key = jax.random.PRNGKey(2)
    X, y = _blobs(key, n=200)
    Xs = jnp.stack([X, X + 0.1])
    ys = jnp.stack([y, y])
    ws = jnp.ones(ys.shape, jnp.float32)
    spec = LearnerSpec(name, X.shape[1], 3, HPARAMS[name])
    learner = get_learner(name)
    keys = jax.random.split(key, 2)
    stacked = jax.vmap(lambda X, y, w, k: learner.fit(spec, None, X, y, w, k))(Xs, ys, ws, keys)
    leaf = jax.tree.leaves(stacked)[0]
    assert leaf.shape[0] == 2
    preds = jax.vmap(lambda p, X: learner.predict(spec, p, X))(stacked, Xs)
    assert preds.shape == (2, 200)


def test_tree_histogram_matches_kernel_oracle():
    from repro.kernels import ref
    from repro.learners.tree import histogram

    key = jax.random.PRNGKey(3)
    n, d, L, B1, K = 500, 8, 4, 9, 3
    bin_idx = jax.random.randint(key, (n, d), 0, B1)
    leaf = jax.random.randint(key, (n,), 0, L)
    wy = jax.random.uniform(key, (n, K))
    np.testing.assert_allclose(
        np.asarray(histogram(bin_idx, leaf, wy, L, B1 - 1)),
        np.asarray(ref.tree_hist_ref(bin_idx, leaf, wy, L, B1)),
        rtol=1e-5,
    )


def _leaf_np(feature, threshold, X):
    """Row leaves of one oblivious tree, in numpy (the oracle's own walk)."""
    leaf = np.zeros(X.shape[0], np.int64)
    for f, t in zip(feature, threshold):
        leaf = leaf * 2 + (X[:, f] > t)
    return leaf


@pytest.mark.parametrize("K", [2, 26])
@pytest.mark.parametrize("depth", [1, 4, 6])
@pytest.mark.parametrize("name", ["decision_tree", "extra_tree"])
def test_tree_predict_class_equals_logit_argmax(name, depth, K):
    """The trees' ``predict`` (a per-leaf class table and one select per
    leaf) equals ``argmax(tree_predict_logits)`` bit for bit: on fitted
    trees with empty leaves (the prior) and exact class ties, on a
    hand-set table of ties (first max wins), and through
    ``scoring.predict_tensor`` against a gather-then-argmax oracle."""
    from repro.core import scoring
    from repro.learners.tree import TreeParams, tree_predict_logits

    learner = get_learner(name)
    d = 6
    spec = LearnerSpec(name, d, K, {"depth": depth, "n_bins": 8, "max_candidates": 8})
    kx, ky, kt, ke = jax.random.split(jax.random.PRNGKey(depth * 100 + K), 4)
    # 8 distinct rows, each twice with the next label: a leaf holding one
    # pair ties its two classes exactly, and at depth >= 4 most leaves are empty
    X8 = jax.random.normal(kx, (8, d))
    y8 = jax.random.randint(ky, (8,), 0, K)
    Xf, yf = jnp.concatenate([X8, X8]), jnp.concatenate([y8, (y8 + 1) % K])
    wf = jnp.ones(16, jnp.float32)
    fit = jax.jit(lambda w, k: learner.fit(spec, None, Xf, yf, w, k))
    H = 3
    hyps = jax.tree.map(
        lambda *a: jnp.stack(a),
        *[fit(wf.at[h].set(2.0 * h), k)
          for h, k in enumerate(jax.random.split(kt, H))],
    )
    Xe = jnp.concatenate([Xf, 3.0 * jax.random.normal(ke, (256, d))])

    def logit_argmax(p, X):
        return jnp.argmax(tree_predict_logits(spec, p, X), axis=-1).astype(jnp.int32)

    for h in range(H):
        p = jax.tree.map(lambda a: a[h], hyps)
        got = learner.predict(spec, p, Xe)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(logit_argmax(p, Xe)))
    if depth >= 4:  # fewer distinct rows than leaves: empty leaves took the prior
        p0 = jax.tree.map(lambda a: np.asarray(a[0]), hyps)
        occupied = set(_leaf_np(p0.feature, p0.threshold, np.asarray(Xf)).tolist())
        assert len(occupied) < 2**depth

    # a hand-set table: every other leaf ties classes a_l and b_l exactly
    # above the rest (the first max wins), the others have one winner b_l
    # on distinct features with a row in every leaf
    L = 2**depth
    p = TreeParams(jnp.arange(depth, dtype=jnp.int32), jnp.zeros(depth), hyps.leaf_logits[0])
    bits = (np.arange(L)[:, None] >> np.arange(depth)[::-1]) & 1  # [L, depth], MSB first
    Xl = np.zeros((L, d), np.float32)
    Xl[:, :depth] = np.where(bits, 1.0, -1.0)
    Xe = jnp.concatenate([Xe, jnp.asarray(Xl)])
    a_l, b_l = np.arange(L) % K, (np.arange(L) * 7 + 2) % K
    tie = np.arange(L) % 2 == 0
    tied = np.full((L, K), -1.0, np.float32)
    tied[np.arange(L), a_l] = np.where(tie, 0.0, -0.5)
    tied[np.arange(L), b_l] = 0.0
    p = p._replace(leaf_logits=jnp.asarray(tied))
    got = np.asarray(learner.predict(spec, p, Xe))
    leaf = _leaf_np(np.asarray(p.feature), np.asarray(p.threshold), np.asarray(Xe))
    assert set(leaf.tolist()) == set(range(L))
    np.testing.assert_array_equal(got, np.where(tie, np.minimum(a_l, b_l), b_l)[leaf])
    np.testing.assert_array_equal(got, np.asarray(logit_argmax(p, Xe)))

    # the scoring tensor [C, H, n] against an explicit gather, then argmax
    C = 2
    Xs = jnp.stack([Xe, -Xe])
    got = np.asarray(jax.jit(lambda hy, X: scoring.predict_tensor(learner, spec, hy, X))(hyps, Xs))
    hp = jax.tree.map(np.asarray, hyps)
    want = np.stack([
        np.stack([
            np.argmax(hp.leaf_logits[h][_leaf_np(hp.feature[h], hp.threshold[h], np.asarray(Xs[c]))], -1)
            for h in range(H)
        ])
        for c in range(C)
    ])
    assert got.shape == (C, H, Xe.shape[0])
    np.testing.assert_array_equal(got, want)
