"""Public jit'd entry points for the Pallas kernels.

``tree_hist`` dispatches by backend: on the TPU it always runs the MXU
kernel; elsewhere ``use_pallas=True`` runs the kernel in interpret mode
and ``use_pallas=False`` the ref.py oracle, which stays the correctness
ground truth of the CPU tests.

The other ops dispatch kernel vs. pure-jnp oracle on ``use_pallas``:
  * ``use_pallas=True``  — the Pallas kernel; on CPU backends it runs in
    interpret mode (the TPU lowering is the deployment target);
  * ``use_pallas=False`` — the ref.py oracle (used by the dry-run so the
    roofline reads real XLA HLO, and as the correctness ground truth).
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.boost_update import weight_update as _weight_update
from repro.kernels.boost_update import weighted_errors as _weighted_errors
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.tree_hist import tiles as tree_hist_tiles
from repro.kernels.tree_hist import tree_hist as _tree_hist
from repro.kernels.vote_argmax import vote_argmax as _vote_argmax
from repro.obs import metrics as obs_metrics


# incremented while tracing: counts compiled programs, not launches
_M_DISPATCH = obs_metrics.counter(
    "mafl_kernel_dispatch_total",
    "Kernel-or-oracle choices made while tracing, by kernel and path.",
    labels=("kernel", "path"),
)
# incremented while tracing: the one-hot rebuilds of one histogram call
_M_LEAF_BLOCKS = obs_metrics.counter(
    "mafl_kernel_tree_hist_leaf_blocks_total",
    "tree_hist calls traced on the kernel paths, by the level's leaves and "
    "the leaf blocks the kernel splits them into.",
    labels=("n_leaves", "blocks"),
)


def count_dispatch(kernel: str, path: str) -> None:
    """Count one traced choice of ``path`` for ``kernel`` (call while tracing)."""
    _M_DISPATCH.labels(kernel=kernel, path=path).inc()


def _backend() -> str:
    return jax.default_backend()


def _interpret() -> bool:
    return _backend() != "tpu"


def tree_hist(bin_idx, leaf, wy, *, n_leaves, n_bins_p1, use_pallas=False, **kw):
    """Weighted class histogram; accepts [n, d] inputs or a leading
    hypothesis/collaborator batch axis ([H, n, d] — one kernel launch
    for all H fits).  This is the fit-path hot-spot dispatch, reached by
    every fit path: the MXU kernel on the TPU whatever ``use_pallas``
    says; elsewhere the kernel in interpret mode under ``use_pallas``,
    else the oracle."""
    if _backend() == "tpu":
        path = "mxu"
    else:
        path = "interpret" if use_pallas else "oracle"
    count_dispatch("tree_hist", path)
    if path != "oracle":
        n, d = bin_idx.shape[-2:]
        *_, block_l = tree_hist_tiles(n, d, wy.shape[-1], n_leaves, n_bins_p1)
        _M_LEAF_BLOCKS.labels(n_leaves=n_leaves, blocks=-(-n_leaves // block_l)).inc()
        return _tree_hist(
            bin_idx, leaf, wy, n_leaves=n_leaves, n_bins_p1=n_bins_p1,
            interpret=path == "interpret", **kw,
        )
    if bin_idx.ndim == 3:
        return ref.tree_hist_batched_ref(bin_idx, leaf, wy, n_leaves, n_bins_p1)
    return ref.tree_hist_ref(bin_idx, leaf, wy, n_leaves, n_bins_p1)


def weighted_errors(preds, y, w, *, use_pallas=False, **kw):
    if use_pallas:
        return _weighted_errors(preds, y, w, interpret=_interpret(), **kw)
    return ref.weighted_errors_ref(preds, y, w)


def weight_update(w, mis, mask, alpha, *, use_pallas=False, **kw):
    if use_pallas:
        return _weight_update(w, mis, mask, alpha, interpret=_interpret(), **kw)
    return ref.boost_weight_update_ref(w, mis, mask, alpha)


def vote_argmax(preds, alpha, *, n_classes, use_pallas=False, **kw):
    if use_pallas:
        return _vote_argmax(
            preds, alpha, n_classes=n_classes, interpret=_interpret(), **kw
        )
    return ref.vote_argmax_ref(preds, alpha, n_classes)


def attention(q, k, v, *, use_pallas=False, **kw):
    if use_pallas:
        return _flash_attention(q, k, v, interpret=_interpret(), **kw)
    return ref.attention_ref(q, k, v, **kw)
