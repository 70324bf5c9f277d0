"""Seeded tabular data for the benchmark's deployments.

A copy of the program's ``data/synthetic.make_classification`` recipe,
kept here so that the data a cell runs on cannot move under a later
change to the program: Gaussian class clusters on a random low-rank
manifold, embedded in ``d`` dimensions with feature noise and label
flips, standardized with train statistics. It runs on the device as one
jitted call.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed, also past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def _make(key, n_train: int, n_test: int, d: int, K: int, Q: int,
          class_sep: float, label_noise: float):
    n = n_train + n_test
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    informative = max(2, min(d, int(math.ceil(math.log2(K * Q))) + 3))
    centers = jax.random.normal(k1, (K * Q, informative)) * class_sep * 2.0
    y = jax.random.randint(k2, (n,), 0, K)
    cluster = y * Q + jax.random.randint(k3, (n,), 0, Q)
    Xi = centers[cluster] + jax.random.normal(k4, (n, informative))
    A = jax.random.normal(k5, (informative, d)) / jnp.sqrt(informative)
    X = Xi @ A + 0.1 * jax.random.normal(k6, (n, d))
    kf1, kf2 = jax.random.split(k6)
    flip = jax.random.bernoulli(kf1, label_noise, (n,))
    y = jnp.where(flip, jax.random.randint(kf2, (n,), 0, K), y).astype(jnp.int32)
    Xtr, Xte = X[:n_train], X[n_train:]
    mu, sd = jnp.mean(Xtr, axis=0), jnp.std(Xtr, axis=0) + 1e-6
    return (Xtr - mu) / sd, y[:n_train], (Xte - mu) / sd, y[n_train:]


_make_jit = jax.jit(_make, static_argnums=(1, 2, 3, 4, 5, 6, 7))


def make_classification(data: dict, key: jax.Array):
    """(X_train, y_train, X_test, y_test) on the default device, from the
    ``data`` block of a configuration file."""
    return _make_jit(
        key, int(data["n_train"]), int(data["n_test"]), int(data["n_features"]),
        int(data["n_classes"]), int(data.get("n_clusters_per_class", 2)),
        float(data["class_sep"]), float(data["label_noise"]),
    )
