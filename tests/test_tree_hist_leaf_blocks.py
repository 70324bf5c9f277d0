"""The histogram kernel's leaf blocks at the multiclass deployment's
shape (UCI Letter over 8 collaborators: [8, 2000, 16], K = 26, the
32-leaf level of a depth-6 tree), and the counter that records them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import tree_hist as th
from repro.obs import metrics as obs_metrics

H, N, D, K, L, B1 = 8, 2000, 16, 26, 32, 17


def _hist64(bin_idx, leaf, wy):
    """[H, L, d, B+1, K] in float64, by bincount."""
    bin_idx, leaf, wy = (np.asarray(a) for a in (bin_idx, leaf, wy))
    out = np.zeros((H, L, D, B1, K))
    for h in range(H):
        cell = (leaf[h, :, None] * D + np.arange(D)) * B1 + bin_idx[h]  # [n, d]
        for k in range(K):
            w = np.broadcast_to(wy[h, :, k, None].astype(np.float64), cell.shape)
            out[h, ..., k] = np.bincount(cell.ravel(), w.ravel(), L * D * B1).reshape(L, D, B1)
    return out


def test_letter_deepest_level_takes_two_leaf_blocks_and_holds_float32():
    """The derived tiles split the 32 leaves into 2 blocks of 16, and the
    interpreted kernel at those tiles matches a float64 histogram within
    1e-6 of each bucket's sum of |wy|, on boosting-like weights (two
    values a decade apart, as after one AdaBoost round)."""
    _, _, block_l = th.tiles(N, D, K, L, B1)
    assert (block_l, -(-L // block_l)) == (16, 2)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(26), 4)
    bin_idx = jax.random.randint(k1, (H, N, D), 0, B1)
    leaf = jax.random.randint(k2, (H, N), 0, L)
    y = jax.random.randint(k3, (H, N), 0, K)
    w = jnp.where(jax.random.bernoulli(k4, 0.4, (H, N)), 25.0, 1.0) / (H * N * 10.6)
    wy = jax.nn.one_hot(y, K) * w[..., None]
    got = th.tree_hist(bin_idx, leaf, wy, n_leaves=L, n_bins_p1=B1, interpret=True)
    want = _hist64(bin_idx, leaf, wy)
    scale = _hist64(bin_idx, leaf, jnp.abs(wy))
    assert got.shape == (H, L, D, B1, K)
    gap = np.abs(np.asarray(got, np.float64) - want) / np.maximum(scale, 1e-30)
    assert float(gap.max()) < 1e-6


def _leaf_blocks(n_leaves):
    family = obs_metrics.REGISTRY.get("mafl_kernel_tree_hist_leaf_blocks_total")
    return {dict(c.labels)["blocks"]: c.value for c in family.children()
            if dict(c.labels)["n_leaves"] == str(n_leaves)}


@pytest.mark.parametrize("backend,use_pallas,counted", [
    ("tpu", False, True),
    ("cpu", True, True),
    ("cpu", False, False),
])
@pytest.mark.parametrize("n_leaves,blocks", [(16, "1"), (32, "2")])
def test_leaf_blocks_counted_on_the_kernel_paths(monkeypatch, backend, use_pallas, counted,
                                                 n_leaves, blocks):
    """Each traced call on the ``mxu`` or ``interpret`` path counts the
    blocks its level's leaves take at letter's shape; the oracle takes
    no leaf blocks and counts none."""
    monkeypatch.setattr(ops, "_backend", lambda: backend)
    monkeypatch.setattr(ops, "_tree_hist", lambda *a, **kw: jnp.zeros(()))
    before = _leaf_blocks(n_leaves)
    args = (jnp.zeros((H, N, D), jnp.int32), jnp.zeros((H, N), jnp.int32),
            jnp.zeros((H, N, K)))
    jax.eval_shape(lambda *a: ops.tree_hist(*a, n_leaves=n_leaves, n_bins_p1=B1,
                                            use_pallas=use_pallas), *args)
    after = _leaf_blocks(n_leaves)
    added = {b: v - before.get(b, 0.0) for b, v in after.items() if v != before.get(b, 0.0)}
    assert added == ({blocks: 1.0} if counted else {})
