"""Pallas TPU kernel: weighted class-histogram accumulation — the
compute hot-spot of oblivious-tree fitting (learners/tree.py).

GPU gradient-boosting libraries implement this as atomic scatter-adds in
shared memory.  TPUs have no atomics; the TPU-native formulation turns
the scatter into a **one-hot contraction** on the MXU.  Per sample block
of S samples and feature block of ``block_d`` features:

    out[(b, f), (l, j)] += sum_s  [bin[f, s] == b]  *  [leaf[s] == l] * wy3[j, s]
         one-hot  [(B+1)*block_d, S]   x   labels by leaf  [L*J, S]^T

The one-hot over the B+1 bins of every feature of the block is the
operand that streams through the MXU.  The weighted labels, each spread
to its sample's leaf column, are the same for every feature of a sample
block: they are the operand the MXU holds.  Folding the leaf into the
label columns keeps the one-hot at B+1 rows a feature on every level,
so deeper levels cost no more compares or MXU rows than the first.

Precision: ``wy3`` carries each float32 weighted label as three bfloat16
pieces, hi + mid + lo == wy exactly (:func:`split3`), stacked on the
class axis ([3K] rows, padded to J).  One-hot entries are exact in
bfloat16, so every product is exact and the MXU accumulates in float32:
one bfloat16 pass instead of the six of ``Precision.HIGHEST``, and no
product or partial sum below float32.  The three pieces' histograms are
summed in float32 at the end.

Layout: samples sit on the 128-wide lane axis of every input (bins as
``[H, d, n]``, leaves as ``[H, 1, n]``, labels as ``[H, J, n]``).  The
tiles are derived from the shape (:func:`tiles`): features in multiples
of 16 sublanes so that the one-hot's ``[B+1, block_d, S]`` -> rows
reshape is free, samples in multiples of 128 lanes, and leaves in blocks
of ``block_l``, so that the one-hot, the labels spread to their leaves
and the output block each stay within their share of the scoped VMEM
limit at every depth.  Deep levels (many leaves) take
several leaf blocks, each rebuilding the sample block's one-hot.

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
# 16 MiB; the float32 one-hot and its bfloat16 copy, the labels spread to
# their leaves (float32 and bfloat16), the output block (double-buffered
# by the pipeline, plus the dot's result) and the small input blocks stay
# inside it.
_ONEHOT_BYTES = 2 * 1024 * 1024
_LABEL_BYTES = 2 * 1024 * 1024
_OUT_BYTES = 2 * 1024 * 1024
_MAX_BLOCK_S = 2048
_PIECES = 3  # bfloat16 pieces of one float32 label


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _label_rows(K: int) -> int:
    """J: rows of the stacked label pieces, 3K padded to bf16's 16 sublanes."""
    return _round_up(_PIECES * K, 16)


def tiles(n: int, d: int, K: int, n_leaves: int, n_bins_p1: int) -> tuple[int, int, int]:
    """Shape-derived ``(block_s, block_d, block_l)``.

    Leaves: as many as keep an output block of 16 features within its
    budget; when the level needs several leaf blocks, each is a multiple
    of 8 leaves (its columns a multiple of 128 lanes) and the blocks are
    even.  Features in multiples of 16 sublanes, as many as the output
    block's budget allows.  Samples in multiples of 128 lanes, as many as
    the one-hot's and the spread labels' budgets allow, split into blocks
    of even size so that the padding stays under 128 samples a block."""
    J = _label_rows(K)
    max_l = max(1, _OUT_BYTES // (16 * n_bins_p1 * 4 * J))
    if n_leaves <= max_l:
        block_l = n_leaves
    else:
        nl = -(-n_leaves // max(8, max_l // 8 * 8))
        block_l = _round_up(-(-n_leaves // nl), 8)
    out_cols = _round_up(block_l * J, 128)
    max_rows = max(16 * n_bins_p1, _OUT_BYTES // (out_cols * 4))
    block_d = min(_round_up(d, 16), max_rows // n_bins_p1 // 16 * 16)
    max_s = min(_ONEHOT_BYTES // (n_bins_p1 * block_d * 4), _LABEL_BYTES // (block_l * J * 6))
    max_s = max(128, min(max_s // 128 * 128, _MAX_BLOCK_S))
    block_s = _round_up(-(-n // -(-n // max_s)), 128)  # even blocks, least padding
    return block_s, block_d, block_l


def split3(wy: jax.Array) -> jax.Array:
    """[..., K] float32 -> [..., 3K]: hi, mid, lo pieces, each exact in
    bfloat16, with hi + mid + lo == wy.  Each piece keeps the top 8
    significant bits of what the pieces before it left (a truncating
    mask on the bits, so no compiler pass may fold the rounding away)."""

    def top8(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    hi = top8(wy)
    mid = top8(wy - hi)
    lo = wy - hi - mid  # at most 8 significant bits are left
    return jnp.concatenate([hi, mid, lo], axis=-1)


def _kernel(bin_ref, leaf_ref, wy_ref, out_ref, *, block_l: int, n_bins_p1: int):
    li, si = pl.program_id(2), pl.program_id(3)

    @pl.when(si == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bins = bin_ref[0]  # [bd, S] i32
    bd, S = bins.shape
    bucket = jax.lax.broadcasted_iota(jnp.int32, (n_bins_p1, bd, S), 0)
    onehot = (bins[None] == bucket).astype(jnp.float32)  # [B+1, bd, S]
    onehot = onehot.reshape(n_bins_p1 * bd, S).astype(jnp.bfloat16)

    wy = wy_ref[0]  # [J, S] f32, each value exact in bf16
    J = wy.shape[0]
    leaf_id = jax.lax.broadcasted_iota(jnp.int32, (block_l, 1, S), 0) + li * block_l
    labels = jnp.where(leaf_ref[0][None] == leaf_id, wy[None], 0.0)  # [bl, J, S]
    labels = labels.reshape(block_l * J, S).astype(jnp.bfloat16)

    # [(B+1)*bd, S] x [bl*J, S]^T -> [(B+1)*bd, bl*J]; exact products, f32 sums
    out_ref[0, 0] += jax.lax.dot_general(
        onehot, labels, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


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
    small ones to exercise multi-block grids in interpret mode); for the
    TPU an override is rounded up to the tiles' lane and sublane
    multiples."""
    squeeze = bin_idx.ndim == 2
    if squeeze:
        bin_idx, leaf, wy = bin_idx[None], leaf[None], wy[None]
    H, n, d = bin_idx.shape
    K = wy.shape[2]
    J = _label_rows(K)
    auto_s, auto_d, block_l = tiles(n, d, K, n_leaves, n_bins_p1)
    lanes, sublanes = (1, 1) if interpret else (128, 16)
    block_s = min(_round_up(block_s, lanes), n) if block_s else auto_s
    block_d = min(_round_up(block_d, sublanes), _round_up(d, sublanes)) if block_d else auto_d

    # Pad to block multiples; padded samples get leaf 0 / weight 0 (no-ops),
    # padded features (bin 0) land in extra feature rows and padded leaves
    # in extra columns, both sliced off below.
    ns = -(-n // block_s)
    nd = -(-d // block_d)
    nl = -(-n_leaves // block_l)
    n_pad, d_pad = ns * block_s, nd * block_d
    bins_t = jnp.pad(bin_idx, ((0, 0), (0, n_pad - n), (0, d_pad - d))).transpose(0, 2, 1)
    leaf_t = jnp.pad(leaf, ((0, 0), (0, n_pad - n)))[:, None, :]
    wy3 = split3(wy.astype(jnp.float32))  # [H, n, 3K]
    wy_t = jnp.pad(wy3, ((0, 0), (0, n_pad - n), (0, J - 3 * K))).transpose(0, 2, 1)

    rows, cols = n_bins_p1 * block_d, block_l * J
    out = pl.pallas_call(
        functools.partial(_kernel, block_l=block_l, n_bins_p1=n_bins_p1),
        grid=(H, nd, nl, ns),
        in_specs=[
            pl.BlockSpec((1, block_d, block_s), lambda h, di, li, si: (h, di, si)),
            pl.BlockSpec((1, 1, block_s), lambda h, di, li, si: (h, 0, si)),
            pl.BlockSpec((1, J, block_s), lambda h, di, li, si: (h, 0, si)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, cols), lambda h, di, li, si: (h, di, 0, li)),
        out_shape=jax.ShapeDtypeStruct((H, nd, rows, nl * cols), jnp.float32),
        interpret=interpret,
        name="tree_hist",
    )(bins_t, leaf_t, wy_t)
    # [H, nd, (B+1, bd), (L, J)] -> the three pieces summed -> [H, L, d, B+1, K]
    L_pad = nl * block_l
    out = out.reshape(H, nd, n_bins_p1, block_d, L_pad, J)[..., : _PIECES * K]
    out = out.reshape(H, nd, n_bins_p1, block_d, L_pad, _PIECES, K).sum(axis=5)
    out = out.transpose(0, 4, 1, 3, 2, 5).reshape(H, L_pad, d_pad, n_bins_p1, K)
    out = out[:, :n_leaves, :d]
    return out[0] if squeeze else out
