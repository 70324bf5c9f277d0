"""Pallas TPU kernel for the serving-path vote reduction:

  ``vote_argmax`` — pred[n] = argmax_k sum_t alpha_t * 1[preds[t, n] == k]

the alpha-weighted majority vote that turns the ensemble members'
class predictions into the strong hypothesis's answer (paper Fig. 1,
inference side).  At serve time this is the only reduction between the
per-member predicts and the response, so it pairs with the
``boost_update`` kernels the same way inference pairs with training.

The member axis (innermost grid dim) sweeps while a ``[K, Nblk]`` vote
accumulator stays resident; the final member block writes the argmax.
Samples sit on the lane axis and members on sublanes, with alpha as a
``[T, 1]`` column, so the class loop builds only 2-D ``[Tblk, Nblk]``
tiles.  Padded members carry alpha == 0 and therefore vote with weight
zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _vote_kernel(preds_ref, alpha_ref, out_ref, votes_ref, *, n_classes):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        votes_ref[...] = jnp.zeros_like(votes_ref)

    p = preds_ref[...]  # [Tblk, Nblk] i32
    a = alpha_ref[...].astype(jnp.float32)  # [Tblk, 1]
    for k in range(n_classes):
        votes_ref[pl.ds(k, 1), :] += jnp.sum(
            jnp.where(p == k, a, 0.0), axis=0, keepdims=True
        )

    @pl.when(ti == pl.num_programs(1) - 1)
    def _finish():
        # first maximum wins, as in jnp.argmax
        best = votes_ref[pl.ds(0, 1), :]
        arg = jnp.zeros(best.shape, jnp.int32)
        for k in range(1, n_classes):
            v = votes_ref[pl.ds(k, 1), :]
            arg = jnp.where(v > best, k, arg)
            best = jnp.maximum(v, best)
        out_ref[...] = arg


@functools.partial(
    jax.jit, static_argnames=("n_classes", "block_t", "block_n", "interpret")
)
def vote_argmax(
    preds: jax.Array,  # [T, n] i32 — per-member class predictions
    alpha: jax.Array,  # [T] f32 — member weights (unused slots = 0)
    *,
    n_classes: int,
    block_t: int = 256,
    block_n: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    T, n = preds.shape
    block_t = min(block_t, T)
    block_n = min(block_n, n)
    nt, nn = -(-T // block_t), -(-n // block_n)
    tp, np_ = nt * block_t, nn * block_n
    # Padded members: alpha = 0 (vote with zero weight). Padded samples
    # produce garbage columns that are sliced off below.
    preds = jnp.pad(preds, ((0, tp - T), (0, np_ - n)))
    alpha = jnp.pad(alpha, (0, tp - T))[:, None]
    out = pl.pallas_call(
        functools.partial(_vote_kernel, n_classes=n_classes),
        grid=(nn, nt),
        in_specs=[
            pl.BlockSpec((block_t, block_n), lambda ni, ti: (ti, ni)),
            pl.BlockSpec((block_t, 1), lambda ni, ti: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda ni, ti: (0, ni)),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.int32),
        scratch_shapes=[pltpu.VMEM((n_classes, block_n), jnp.float32)],
        interpret=interpret,
    )(preds, alpha)
    return out[0, :n]
