"""Pallas TPU kernel: weighted class-histogram accumulation — the
compute hot-spot of oblivious-tree fitting (learners/tree.py).

GPU gradient-boosting libraries implement this as atomic scatter-adds in
shared memory.  TPUs have no atomics; the TPU-native formulation turns
the scatter into a **one-hot matmul** that runs on the MXU:

    for each feature f in the block:
        C[f] += wy.T  @  onehot(leaf * (B+1) + bin[f, :]).T     # [K, S] @ [S, M]

with M = n_leaves * (B+1) combined (leaf, bin) buckets.  The grid walks
(batch) x (feature blocks) x (sample blocks); the sample axis is
innermost so each output tile stays resident in VMEM while samples
stream through.

Layout: samples sit on the 128-wide lane axis of every input (bins as
``[H, d, n]``, leaves as ``[H, 1, n]``, weighted labels as ``[H, K, n]``),
so every block's last two dims are either full or (8, 128)-aligned, and
each feature's one-hot is a 2-D ``[M, S]`` tile built from one sublane
row — never a rank-3 one-hot.  The tiles are derived from the shape
(:func:`tiles`) so the one-hot and the double-buffered blocks stay well
under the scoped VMEM limit at every width the federation uses.

The leading batch axis folds the federation's C collaborators (one local
tree fit each, same tree level) into the SAME grid, so one fused
AdaBoost.F round issues ONE kernel launch per tree level instead of C —
see ``learners/tree.py::fit_tree_batched``.  2-D inputs (a single fit)
are the batch=1 special case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Per-buffer VMEM budgets (bytes).  v5e's default scoped VMEM limit is
# 16 MiB; the one-hot (plus its iota), the output tile and the input
# blocks — each double-buffered by the pipeline — stay well inside it.
_ONEHOT_BYTES = 2 * 1024 * 1024
_OUT_BYTES = 2 * 1024 * 1024
_MAX_BLOCK_S = 2048


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tiles(n: int, d: int, K: int, M: int) -> tuple[int, int]:
    """Shape-derived ``(block_s, block_d)``: samples in multiples of 128
    lanes, features in multiples of 8 sublanes (or all of them)."""
    block_s = _ONEHOT_BYTES // (_round_up(M, 8) * 4) // 128 * 128
    block_s = max(128, min(block_s, _MAX_BLOCK_S, _round_up(n, 128)))
    per_feature = _round_up(K, 8) * _round_up(M, 128) * 4
    block_d = d if d * per_feature <= _OUT_BYTES else max(8, _OUT_BYTES // per_feature // 8 * 8)
    return block_s, block_d


def _kernel(bin_ref, leaf_ref, wy_ref, out_ref, *, n_bins_p1: int, M: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    base = leaf_ref[0] * n_bins_p1  # [1, S] i32
    wy = wy_ref[0].astype(jnp.float32)  # [K, S]
    buckets = jax.lax.broadcasted_iota(jnp.int32, (M, base.shape[1]), 0)

    def feature(f, carry):
        idx = base + bin_ref[0, pl.ds(f, 1), :]  # [1, S]
        onehot = (buckets == idx).astype(jnp.float32)  # [M, S]
        # [K, S] x [M, S]^T -> [K, M] on the MXU, f32-exact accumulation
        out_ref[0, f] += jax.lax.dot_general(
            wy, onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        return carry

    jax.lax.fori_loop(0, bin_ref.shape[1], feature, 0)


@functools.partial(
    jax.jit, static_argnames=("n_leaves", "n_bins_p1", "block_s", "block_d", "interpret")
)
def tree_hist(
    bin_idx: jax.Array,  # [n, d] or [H, n, d] i32 in [0, n_bins]
    leaf: jax.Array,  # [n] or [H, n] i32
    wy: jax.Array,  # [n, K] or [H, n, K] f32
    *,
    n_leaves: int,
    n_bins_p1: int,
    block_s: int | None = None,
    block_d: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Returns C[L, d, B+1, K] (or [H, L, d, B+1, K] with a leading
    hypothesis/collaborator batch axis); oracle: kernels/ref.py.

    ``block_s``/``block_d`` override the shape-derived tiles (tests use
    small ones to exercise multi-block grids in interpret mode)."""
    squeeze = bin_idx.ndim == 2
    if squeeze:
        bin_idx, leaf, wy = bin_idx[None], leaf[None], wy[None]
    H, n, d = bin_idx.shape
    K = wy.shape[2]
    M = n_leaves * n_bins_p1
    auto_s, auto_d = tiles(n, d, K, M)
    block_s = min(block_s or auto_s, n)
    block_d = min(block_d or auto_d, d)

    # Pad to block multiples; padded samples get leaf 0 / weight 0 (no-ops),
    # padded features land in extra feature rows that are sliced off below.
    ns = -(-n // block_s)
    nd = -(-d // block_d)
    n_pad, d_pad = ns * block_s, nd * block_d
    bins_t = jnp.pad(bin_idx, ((0, 0), (0, n_pad - n), (0, d_pad - d))).transpose(0, 2, 1)
    leaf_t = jnp.pad(leaf, ((0, 0), (0, n_pad - n)))[:, None, :]
    wy_t = jnp.pad(wy, ((0, 0), (0, n_pad - n), (0, 0))).transpose(0, 2, 1)

    out = pl.pallas_call(
        functools.partial(_kernel, n_bins_p1=n_bins_p1, M=M),
        grid=(H, nd, ns),
        in_specs=[
            pl.BlockSpec((1, block_d, block_s), lambda h, di, si: (h, di, si)),
            pl.BlockSpec((1, 1, block_s), lambda h, di, si: (h, 0, si)),
            pl.BlockSpec((1, K, block_s), lambda h, di, si: (h, 0, si)),
        ],
        out_specs=pl.BlockSpec((1, block_d, K, M), lambda h, di, si: (h, di, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((H, d_pad, K, M), jnp.float32),
        interpret=interpret,
    )(bins_t, leaf_t, wy_t)
    # [H, d, K, L*(B+1)] -> [H, L, d, B+1, K]
    out = out[:, :d].reshape(H, d, K, n_leaves, n_bins_p1).transpose(0, 3, 1, 4, 2)
    return out[0] if squeeze else out
