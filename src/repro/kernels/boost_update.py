"""Pallas TPU kernels for the AdaBoost.F inner loop (paper steps 3-4):

  * ``weighted_errors``   — eps[h] = sum_n w_n * [preds[h,n] != y_n]
    (every collaborator scores the WHOLE hypothesis space on its shard,
    so this is H x n work per round — the round's reduction hot-spot);
  * ``weight_update``     — w <- w * exp(alpha * mis) * mask, fused.

Both stream samples through VMEM tiles; the error kernel keeps an
[Hblk, 1] accumulator tile resident while the sample axis (innermost
grid dim) sweeps.  Samples sit on the lane axis (``y``/``w`` as
``[1, n]`` rows), so every block is full or (8, 128)-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_ERR_TILE_BYTES = 2 * 1024 * 1024  # one [Hblk, S] i32 prediction tile


def _err_kernel(preds_ref, y_ref, w_ref, out_ref):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    mis = (preds_ref[...] != y_ref[...]).astype(jnp.float32)  # [Hblk, S]
    # a lane reduction on the VPU, not an MXU matvec: f32-exact products
    out_ref[...] += jnp.sum(mis * w_ref[...].astype(jnp.float32), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_h", "block_s", "interpret"))
def weighted_errors(
    preds: jax.Array,  # [H, n] i32
    y: jax.Array,  # [n] i32
    w: jax.Array,  # [n] f32
    *,
    block_h: int | None = None,
    block_s: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``block_h``/``block_s`` override the shape-derived tiles (all
    hypotheses up to 256 in one block; samples in multiples of 128)."""
    H, n = preds.shape
    block_h = min(block_h or 256, H)
    rows = -(-block_h // 8) * 8  # hypotheses padded to whole sublane tiles
    auto_s = max(128, _ERR_TILE_BYTES // (4 * rows) // 128 * 128)
    block_s = min(block_s or auto_s, n)
    nh, ns = -(-H // block_h), -(-n // block_s)
    hp, np_ = nh * block_h, ns * block_s
    # Padded samples: w = 0 (no contribution). Padded hypotheses sliced off.
    preds = jnp.pad(preds, ((0, hp - H), (0, np_ - n)))
    y = jnp.pad(y, (0, np_ - n), constant_values=-1)[None, :]
    w = jnp.pad(w, (0, np_ - n))[None, :]
    out = pl.pallas_call(
        _err_kernel,
        grid=(nh, ns),
        in_specs=[
            pl.BlockSpec((block_h, block_s), lambda hi, si: (hi, si)),
            pl.BlockSpec((1, block_s), lambda hi, si: (0, si)),
            pl.BlockSpec((1, block_s), lambda hi, si: (0, si)),
        ],
        out_specs=pl.BlockSpec((block_h, 1), lambda hi, si: (hi, 0)),
        out_shape=jax.ShapeDtypeStruct((hp, 1), jnp.float32),
        interpret=interpret,
    )(preds, y, w)
    return out[:H, 0]


def _upd_kernel(w_ref, mis_ref, mask_ref, alpha_ref, out_ref):
    alpha = alpha_ref[0]
    out_ref[...] = w_ref[...] * jnp.exp(alpha * mis_ref[...]) * mask_ref[...]


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def weight_update(
    w: jax.Array,  # [n] f32
    mis: jax.Array,  # [n] f32
    mask: jax.Array,  # [n] f32
    alpha: jax.Array,  # scalar f32
    *,
    block_s: int = 4096,
    interpret: bool = False,
) -> jax.Array:
    n = w.shape[0]
    block_s = min(block_s, n)
    ns = -(-n // block_s)
    np_ = ns * block_s
    pad = lambda a: jnp.pad(a, (0, np_ - n))
    out = pl.pallas_call(
        _upd_kernel,
        grid=(ns,),
        in_specs=[
            pl.BlockSpec((block_s,), lambda si: (si,)),
            pl.BlockSpec((block_s,), lambda si: (si,)),
            pl.BlockSpec((block_s,), lambda si: (si,)),
            pl.BlockSpec((1,), lambda si: (0,)),
        ],
        out_specs=pl.BlockSpec((block_s,), lambda si: (si,)),
        out_shape=jax.ShapeDtypeStruct((np_,), w.dtype),
        interpret=interpret,
    )(pad(w), pad(mis), pad(mask), jnp.reshape(alpha, (1,)))
    return out[:n]
