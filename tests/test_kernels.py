"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracle in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.boost_update import weight_update, weighted_errors
from repro.kernels.flash_attention import flash_attention
from repro.kernels import tree_hist as th
from repro.kernels.tree_hist import split3, tree_hist
from repro.obs import metrics as obs_metrics


@pytest.mark.parametrize("n,d,L,B1,K", [
    (257, 5, 2, 9, 2),      # non-divisible n/d (padding paths)
    (1024, 14, 8, 17, 3),
    (512, 54, 16, 17, 7),
])
def test_tree_hist_sweep(n, d, L, B1, K):
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    bin_idx = jax.random.randint(k1, (n, d), 0, B1)
    leaf = jax.random.randint(k2, (n,), 0, L)
    wy = jax.random.uniform(k3, (n, K))
    got = tree_hist(bin_idx, leaf, wy, n_leaves=L, n_bins_p1=B1,
                    block_s=128, block_d=4, interpret=True)
    want = ref.tree_hist_ref(bin_idx, leaf, wy, L, B1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("C,n,d,L,B1,K", [
    (3, 257, 5, 2, 9, 2),    # ragged n/d (padding paths) under the batch axis
    (5, 130, 7, 8, 17, 3),   # n smaller than block_s, d ragged vs block_d
])
def test_tree_hist_batched_sweep(C, n, d, L, B1, K):
    """The leading hypothesis/collaborator axis folds into the kernel
    grid: one launch must equal the per-slice oracle stack."""
    key = jax.random.PRNGKey(5)
    k1, k2, k3 = jax.random.split(key, 3)
    bin_idx = jax.random.randint(k1, (C, n, d), 0, B1)
    leaf = jax.random.randint(k2, (C, n), 0, L)
    wy = jax.random.uniform(k3, (C, n, K))
    got = tree_hist(bin_idx, leaf, wy, n_leaves=L, n_bins_p1=B1,
                    block_s=64, block_d=4, interpret=True)
    want = ref.tree_hist_batched_ref(bin_idx, leaf, wy, L, B1)
    assert got.shape == (C, L, d, B1, K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    # batched oracle == stack of single-slice oracles (bit-for-bit: the
    # batched fit path must not change what one collaborator computes)
    per_slice = np.stack([
        np.asarray(ref.tree_hist_ref(bin_idx[c], leaf[c], wy[c], L, B1))
        for c in range(C)
    ])
    np.testing.assert_array_equal(np.asarray(want), per_slice)


def test_tree_hist_zero_weight_rows_are_noops():
    """Masked/padded samples carry w == 0 and must not contribute —
    including the rows the kernel itself pads up to a block multiple."""
    key = jax.random.PRNGKey(6)
    n, d, L, B1, K = 200, 6, 4, 9, 3
    k1, k2, k3 = jax.random.split(key, 3)
    bin_idx = jax.random.randint(k1, (n, d), 0, B1)
    leaf = jax.random.randint(k2, (n,), 0, L)
    wy = jax.random.uniform(k3, (n, K))
    keep = (jnp.arange(n) < n - 37).astype(jnp.float32)  # zero-weight tail
    wy_masked = wy * keep[:, None]
    got = tree_hist(bin_idx, leaf, wy_masked, n_leaves=L, n_bins_p1=B1,
                    block_s=64, block_d=4, interpret=True)
    # dropping the zero-weight rows entirely must give the same histogram
    m = n - 37
    want = ref.tree_hist_ref(bin_idx[:m], leaf[:m], wy[:m], L, B1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    # all-zero weights -> identically zero histogram
    zero = tree_hist(bin_idx, leaf, jnp.zeros_like(wy), n_leaves=L, n_bins_p1=B1,
                     block_s=64, block_d=4, interpret=True)
    assert float(jnp.max(jnp.abs(zero))) == 0.0


def test_tree_hist_batched_kernel_matches_singles():
    """Kernel with the batch axis == the same kernel run slice by slice."""
    key = jax.random.PRNGKey(7)
    C, n, d, L, B1, K = 4, 96, 5, 2, 5, 2
    k1, k2, k3 = jax.random.split(key, 3)
    bin_idx = jax.random.randint(k1, (C, n, d), 0, B1)
    leaf = jax.random.randint(k2, (C, n), 0, L)
    wy = jax.random.uniform(k3, (C, n, K))
    batched = tree_hist(bin_idx, leaf, wy, n_leaves=L, n_bins_p1=B1,
                        block_s=32, block_d=4, interpret=True)
    for c in range(C):
        single = tree_hist(bin_idx[c], leaf[c], wy[c], n_leaves=L, n_bins_p1=B1,
                           block_s=32, block_d=4, interpret=True)
        np.testing.assert_allclose(
            np.asarray(batched[c]), np.asarray(single), atol=1e-5
        )


def _six_decade_inputs(key, H, n, d, L, B1, K, n_zero):
    """Bins, leaves and weighted one-hot labels whose weights spread over
    six decades (so every bf16 piece of a label carries bits), with a
    zero-weight tail of ``n_zero`` rows per slice."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    bin_idx = jax.random.randint(k1, (H, n, d), 0, B1)
    leaf = jax.random.randint(k2, (H, n), 0, L)
    y = jax.random.randint(k3, (H, n), 0, K)
    w = jax.random.uniform(k4, (H, n), minval=0.5, maxval=1.0)
    w = w * 10.0 ** jax.random.uniform(k5, (H, n), minval=-6.0, maxval=0.0)
    w = w * (jnp.arange(n) < n - n_zero)
    return bin_idx, leaf, jax.nn.one_hot(y, K) * w[..., None]


def _bucket_gap(got, want, bin_idx, leaf, wy, L, B1):
    """Largest |got - want| of a bucket over the bucket's sum of |wy|."""
    scale = np.asarray(ref.tree_hist_batched_ref(bin_idx, leaf, jnp.abs(wy), L, B1))
    gap = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    return float(np.max(gap / np.maximum(scale, 1e-30)))


@pytest.mark.parametrize("K", [2, 26])
@pytest.mark.parametrize("L", [1, 2, 4, 8, 32, 128])
def test_tree_hist_levels_hold_float32(L, K):
    """Every level's M = L x 17 buckets at K = 2 and K = 26 (a depth-4
    tree's levels, and deep levels whose leaves take several leaf
    blocks), n ragged against the tile, zero-weight tail rows: each
    bucket within 1e-5 of the oracle relative to its sum of |wy|.  Labels
    rounded once to bf16 miss by about 4e-3, so this tells the
    three-piece split apart."""
    H, n, d, B1 = 2, 300, 6, 17
    bin_idx, leaf, wy = _six_decade_inputs(
        jax.random.PRNGKey(10 + L + K), H, n, d, L, B1, K, n_zero=29
    )
    got = tree_hist(bin_idx, leaf, wy, n_leaves=L, n_bins_p1=B1,
                    block_s=128, interpret=True)
    want = ref.tree_hist_batched_ref(bin_idx, leaf, wy, L, B1)
    assert got.shape == (H, L, d, B1, K)
    assert _bucket_gap(got, want, bin_idx, leaf, wy, L, B1) < 1e-5
    rounded = ref.tree_hist_batched_ref(
        bin_idx, leaf, wy.astype(jnp.bfloat16).astype(jnp.float32), L, B1
    )
    assert _bucket_gap(rounded, want, bin_idx, leaf, wy, L, B1) > 1e-4


@pytest.mark.parametrize("n,d,K", [
    (4071, 14, 2),    # adult over 8
    (7262, 54, 2),    # covtype over 64
    (116202, 54, 2),  # covtype over 4, one shard a chip
    (2000, 16, 26),   # letter over 8
])
def test_tree_hist_tiles_fit_scoped_vmem(n, d, K):
    """At every level of a depth-8 tree the derived tiles keep the
    kernel's VMEM (one-hot in f32 and bf16, labels spread to their leaves
    in f32 and bf16, the output block double-buffered plus the dot's
    result, the double-buffered inputs) well inside v5e's 16 MiB scoped
    limit, on TPU-legal block shapes.  The compile for a described chip
    does not check VMEM, so this is the guard."""
    B1 = 17
    J = th._label_rows(K)
    for L in (2 ** lv for lv in range(8)):
        bs, bd, bl = th.tiles(n, d, K, L, B1)
        assert bs % 128 == 0 and bd % 16 == 0
        assert bl == L or (bl % 8 == 0 and bl < L)
        rows, cols = B1 * bd, -(-bl * J // 128) * 128
        vmem = (B1 * bd * bs * (4 + 2) + bl * J * bs * (4 + 2)
                + 3 * rows * cols * 4 + 2 * (bd + 8 + J) * bs * 4)
        assert vmem < 12 * 2 ** 20, (L, bs, bd, bl, vmem)


def test_tree_hist_split3_is_exact():
    """hi + mid + lo == wy bit for bit, and every piece is a bf16 value."""
    x = jax.random.uniform(jax.random.PRNGKey(11), (4096, 3), minval=-1.0, maxval=1.0)
    x = x * 10.0 ** jax.random.uniform(jax.random.PRNGKey(12), (4096, 3), minval=-30, maxval=30)
    p = split3(x)
    assert bool(jnp.all(p[:, :3] + p[:, 3:6] + p[:, 6:] == x))
    assert bool(jnp.all(p.astype(jnp.bfloat16).astype(jnp.float32) == p))


def _dispatches(path, kernel="tree_hist"):
    family = obs_metrics.REGISTRY.get("mafl_kernel_dispatch_total")
    for child in family.children() if family else ():
        if dict(child.labels) == {"kernel": kernel, "path": path}:
            return child.value
    return 0.0


@pytest.mark.parametrize("backend,use_pallas,path", [
    ("tpu", False, "mxu"),
    ("tpu", True, "mxu"),
    ("cpu", False, "oracle"),
    ("cpu", True, "interpret"),
])
def test_tree_hist_dispatch_by_backend(monkeypatch, backend, use_pallas, path):
    """The TPU takes the MXU kernel whatever ``use_pallas`` says; the CPU
    takes the oracle unless ``use_pallas`` asks for the interpreted
    kernel.  Each traced choice is counted under its path.  The other
    kernels keep dispatching on ``use_pallas`` alone."""
    calls = []

    def spy(*a, **kw):
        calls.append(kw["interpret"])
        return jnp.zeros(())

    monkeypatch.setattr(ops, "_backend", lambda: backend)
    monkeypatch.setattr(ops, "_tree_hist", spy)
    before = _dispatches(path)
    bin_idx = jnp.zeros((4, 3), jnp.int32)
    out = ops.tree_hist(bin_idx, jnp.zeros(4, jnp.int32), jnp.ones((4, 2)),
                        n_leaves=1, n_bins_p1=5, use_pallas=use_pallas)
    assert _dispatches(path) == before + 1
    if path == "oracle":
        assert calls == []
        assert out.shape == (1, 3, 5, 2)
    else:
        assert calls == [path == "interpret"]

    errs = []
    monkeypatch.setattr(ops, "_weighted_errors", lambda *a, **kw: errs.append(kw))
    preds = jnp.zeros((2, 4), jnp.int32)
    ops.weighted_errors(preds, jnp.zeros(4, jnp.int32), jnp.ones(4), use_pallas=False)
    assert errs == []
    ops.weighted_errors(preds, jnp.zeros(4, jnp.int32), jnp.ones(4), use_pallas=True)
    assert errs == [{"interpret": backend != "tpu"}]


@pytest.mark.parametrize("name,counted", [
    ("decision_tree", 1),
    ("extra_tree", 1),
    ("ridge", 0),
    ("gaussian_nb", 0),
])
def test_tree_predict_leaf_select_dispatch_is_counted(name, counted):
    """Tracing a tree's ``predict`` counts one ``tree_predict`` /
    ``leaf_select`` choice, under vmap as well; a learner without
    ``predict_class`` traces its logit argmax and counts nothing."""
    from repro.learners import LearnerSpec, get_learner

    learner = get_learner(name)
    spec = LearnerSpec(name, 5, 3, {"depth": 4})
    params = learner.init(spec, jax.random.PRNGKey(0))
    X = jnp.zeros((7, 5))
    before = _dispatches("leaf_select", kernel="tree_predict")
    jax.make_jaxpr(lambda p, X: learner.predict(spec, p, X))(params, X)
    assert _dispatches("leaf_select", kernel="tree_predict") == before + counted
    stacked = jax.tree.map(lambda a: jnp.stack([a, a]), params)
    jax.make_jaxpr(jax.vmap(lambda p: learner.predict(spec, p, X)))(stacked)
    assert _dispatches("leaf_select", kernel="tree_predict") == before + 2 * counted


@pytest.mark.parametrize("H,n", [(3, 100), (8, 1000), (33, 4096)])
def test_weighted_errors_sweep(H, n):
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    preds = jax.random.randint(k1, (H, n), 0, 5)
    y = jax.random.randint(k2, (n,), 0, 5)
    w = jax.random.uniform(k3, (n,))
    got = weighted_errors(preds, y, w, block_h=4, block_s=256, interpret=True)
    want = ref.weighted_errors_ref(preds, y, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4)


@pytest.mark.parametrize("n,alpha", [(100, 0.5), (4097, 2.0), (64, -1.0)])
def test_weight_update_sweep(n, alpha):
    key = jax.random.PRNGKey(2)
    w = jax.random.uniform(key, (n,))
    mis = jax.random.bernoulli(key, 0.4, (n,)).astype(jnp.float32)
    mask = (jnp.arange(n) < n - 3).astype(jnp.float32)
    got = weight_update(w, mis, mask, jnp.float32(alpha), block_s=128, interpret=True)
    want = ref.boost_weight_update_ref(w, mis, mask, jnp.float32(alpha))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize(
    "B,Hq,Hkv,S,T,D,causal,window,softcap,dtype",
    [
        (2, 4, 2, 128, 128, 64, True, None, None, jnp.float32),
        (1, 4, 1, 128, 128, 64, True, 64, None, jnp.float32),   # MQA + window
        (1, 2, 2, 96, 160, 32, True, None, 30.0, jnp.float32),  # S<T + softcap
        (1, 2, 2, 128, 128, 64, False, None, None, jnp.float32),  # encoder
        (1, 8, 2, 128, 128, 128, True, None, None, jnp.bfloat16),  # bf16
        (1, 2, 2, 100, 100, 64, True, None, None, jnp.float32),  # pad seq
    ],
)
def test_flash_attention_sweep(B, Hq, Hkv, S, T, D, causal, window, softcap, dtype):
    key = jax.random.PRNGKey(3)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, Hq, S, D), dtype)
    k = jax.random.normal(k2, (B, Hkv, T, D), dtype)
    v = jax.random.normal(k3, (B, Hkv, T, D), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window, softcap=softcap,
                          block_q=64, block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )


def test_flash_attention_fully_masked_rows_are_safe():
    """Window smaller than block: early KV blocks fully masked for some
    rows must not produce NaNs (the m=-inf guard)."""
    key = jax.random.PRNGKey(4)
    q = jax.random.normal(key, (1, 2, 256, 32))
    k = jax.random.normal(key, (1, 2, 256, 32))
    v = jax.random.normal(key, (1, 2, 256, 32))
    got = flash_attention(q, k, v, causal=True, window=16, block_q=64, block_k=64,
                          interpret=True)
    assert np.all(np.isfinite(np.asarray(got)))
    want = ref.attention_ref(q, k, v, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
