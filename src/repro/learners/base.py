"""Model-agnostic weak-learner interface — the full registry contract.

MAFL's central claim is that the federated protocol never inspects the
model: a weak hypothesis is an *opaque pytree* plus pure functions. Every
learner in this package implements the ``WeakLearner`` interface below
with **fixed shapes** so that:

  * ``fit`` / ``predict`` jit-compile,
  * ``vmap(fit)`` trains one hypothesis per collaborator in parallel,
  * hypothesis pytrees can be exchanged with ``lax.all_gather`` and stored
    stacked in the ensemble buffer (core/boosting.py).

Sample weights ``w`` implement both AdaBoost weighting and masking
(padded samples carry ``w == 0``); labels are int32 in ``[0, n_classes)``.

The registry contract
---------------------
``register(WeakLearner(...))`` puts a learner behind a string key.  The
key is the learner's identity EVERYWHERE downstream: ``LearnerSpec.name``
selects it for training, the serving artifact manifest records it
(``serve/artifact.py``), and a heterogeneous federation
(``core/hetero.py``) assigns one key per collaborator.  To participate —
including as one group of a mixed federation — an implementation must
satisfy:

  required
    ``init(spec, key) -> params``
        Shape-deterministic: for a fixed ``spec`` the returned pytree's
        treedef and every leaf's shape/dtype must not depend on ``key``
        (keys may only seed *values*).  Artifact loading rebuilds the
        ensemble structure from ``init`` alone, and the ensemble slot
        buffer pre-allocates ``T`` stacked copies of it.
    ``fit(spec, params, X, y, w, key) -> params``
        Pure, fixed-shape: X [n, d] f32, y [n] i32, w [n] f32 (>= 0;
        ``w == 0`` rows are masked padding and must not influence the
        hypothesis).  Must ignore incoming ``params`` values (each
        boosting round fits from scratch) and return a pytree with the
        ``init`` structure.  Must tolerate degenerate weights (an
        all-zero shard must not NaN — guard divisions).
    ``predict_logits(spec, params, X) -> [n, K]``
        Pure per-class scores; ``predict`` takes their argmax.  Must be
        traceable with X batched under vmap AND with ``params`` coming
        from a traced ensemble slot (no host-side indexing).

  optional, unlock specific subsystems
    ``warm_fit``     — gradient-style continuation from broadcast
                       params; REQUIRED only for the FedAvg/DNN workflow
                       (meaningless for closed-form fits; fedavg is also
                       the one workflow heterogeneous federations
                       exclude, since it averages parameters).
    ``precompute`` / ``fit_cached`` — the X-only fit cache (see the
                       field comments below).  Without them a learner
                       still joins every federation; rounds just redo
                       the X-derived scaffold.
    ``fit_batched``  — collaborator-batched fit, one tensor program for
                       all C members of a (sub)federation.  In a
                       heterogeneous federation each learner GROUP runs
                       its own ``fit_batched`` over its members, so a
                       kernel-backed learner keeps its one-launch fit
                       even when mixed with closed-form families.
    ``predict_class`` — the class without the ``[n, K]`` logits (see the
                       field comment below); ``predict`` uses it when
                       set, ``argmax(predict_logits)`` otherwise.

Registering a new learner
-------------------------
A minimal example (a weighted class-prior stump)::

    import jax.numpy as jnp
    from repro.learners.base import (
        LearnerSpec, WeakLearner, register, weighted_onehot,
    )

    def init(spec, key):
        return {"log_prior": jnp.zeros((spec.n_classes,))}

    def fit(spec, params, X, y, w, key):
        del params, key  # fresh fit; key unused by the closed form
        counts = jnp.sum(weighted_onehot(y, w, spec.n_classes), axis=0)
        prior = counts / jnp.maximum(jnp.sum(counts), 1e-12)
        return {"log_prior": jnp.log(prior + 1e-12)}

    def predict_logits(spec, params, X):
        return jnp.broadcast_to(params["log_prior"], (X.shape[0], spec.n_classes))

    prior_stump = register(WeakLearner("prior_stump", init, fit, predict_logits))

After ``register``, ``"prior_stump"`` works everywhere a registry key is
accepted: ``LearnerSpec("prior_stump", ...)``, ``fl_run --learner`` /
``--learners decision_tree,prior_stump,...``, artifact manifests, and
the serving engine.  Registration is process-local: loading an artifact
that names a key requires the defining module to have been imported
(the built-ins auto-register via ``repro.learners``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

Params = Any  # opaque pytree — the whole point of model-agnosticism


@dataclasses.dataclass(frozen=True)
class LearnerSpec:
    """Static description of the learning problem + learner hyperparams."""

    name: str
    n_features: int
    n_classes: int
    hparams: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def hp(self, key: str, default: Any) -> Any:
        return self.hparams.get(key, default)


@dataclasses.dataclass(frozen=True)
class WeakLearner:
    """A weak learner = init + weighted fit + predict_logits.

    ``fit(spec, params, X, y, w, key) -> params`` must be a pure function
    of fixed-shape inputs:  X [n, d] f32, y [n] i32, w [n] f32 (>= 0,
    zero == masked-out).  ``predict_logits(spec, params, X) -> [n, K]``
    returns per-class scores; ``predict`` takes their argmax.
    """

    name: str
    init: Callable[[LearnerSpec, jax.Array], Params]
    fit: Callable[[LearnerSpec, Params, jax.Array, jax.Array, jax.Array, jax.Array], Params]
    predict_logits: Callable[[LearnerSpec, Params, jax.Array], jax.Array]
    # Optional gradient-based warm-start fit (continues from ``params``) —
    # required by the FedAvg/DNN workflow, meaningless for closed-form fits.
    warm_fit: Callable[..., Params] | None = None
    # -- fit-cache contract -------------------------------------------------
    # X is static per collaborator across boosting rounds; only the sample
    # weights change.  A learner may therefore expose an X-only fit
    # precomputation, computed ONCE per shard and threaded through every
    # round as ``BoostState.fit_cache``:
    #
    #   ``precompute(spec, X) -> cache`` returns an ARBITRARY cache pytree
    #   (arrays / NamedTuples / dicts — anything jax.tree handles).  The
    #   trees return a ``learners/binning.py::BinnedDataset`` (quantile
    #   edges + digitized bin indices); other learners can cache whatever
    #   X-derived scaffold their fit reuses (Gram matrices, norms, ...).
    #   The cache must vmap over a leading collaborator axis and cross
    #   shard_map boundaries, i.e. contain only fixed-shape arrays.
    #
    #   ``fit_cached(spec, params, X, y, w, key, cache) -> params`` must
    #   satisfy  fit_cached(..., precompute(spec, X)) == fit(...)
    #   bit-for-bit — the cache is an optimisation, never a semantic knob.
    precompute: Callable[[LearnerSpec, jax.Array], Any] | None = None
    fit_cached: Callable[..., Params] | None = None
    # Optional collaborator-batched fit: one tensor program fits all C
    # local hypotheses of a federated round (kernel-backed learners fold
    # the batch axis into their grid — one launch instead of C).
    #
    #   ``fit_batched(spec, X, y, w, keys, cache, *, use_pallas=...,
    #   block_s=..., block_d=...) -> params`` over [C, ...]-stacked
    #   inputs must equal ``vmap(fit)`` / ``vmap(fit_cached)`` bit-for-bit
    #   when ``use_pallas=False`` (the kernel path agrees to float32
    #   tolerance and is parity-swept in tests/test_kernels.py).
    fit_batched: Callable[..., Params] | None = None
    # Optional direct class prediction: ``predict_class(spec, params, X)
    # -> [n] i32`` must satisfy  predict_class(...) ==
    # argmax(predict_logits(...), -1)  bit-for-bit (first max on ties) —
    # an optimisation, never a semantic knob.  The trees pick each row's
    # class from a per-leaf class table instead of gathering K logits a row.
    predict_class: Callable[[LearnerSpec, Params, jax.Array], jax.Array] | None = None

    def predict(self, spec: LearnerSpec, params: Params, X: jax.Array) -> jax.Array:
        if self.predict_class is not None:
            return self.predict_class(spec, params, X)
        return jnp.argmax(self.predict_logits(spec, params, X), axis=-1).astype(jnp.int32)


_REGISTRY: Dict[str, WeakLearner] = {}


def register(learner: WeakLearner) -> WeakLearner:
    _REGISTRY[learner.name] = learner
    return learner


def get_learner(name: str) -> WeakLearner:
    if name not in _REGISTRY:
        raise KeyError(f"unknown learner {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def available_learners():
    return sorted(_REGISTRY)


def weighted_onehot(y: jax.Array, w: jax.Array, n_classes: int) -> jax.Array:
    """[n] labels + [n] weights -> [n, K] weighted one-hot (masked rows = 0)."""
    return jax.nn.one_hot(y, n_classes, dtype=w.dtype) * w[:, None]
