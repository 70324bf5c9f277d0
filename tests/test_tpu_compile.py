"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e (no chip needed): the TPU compiler refuses illegal block shapes
and VMEM overruns that interpret mode never sees.  Each kernel is
compiled through its public entry point at the widths the fused round
and the serving engine use, and must lower to a Mosaic
``tpu_custom_call``.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import scoring
from repro.kernels import ops

N_BINS_P1 = 17  # 16 quantile bins + the overflow bin
LEAVES = 8  # the widest histogram level of a depth-4 tree


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one; keep them out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def compile_for(one_chip, monkeypatch):
    """``compile_for(fn, *shapes)`` -> compiled HLO text for the chip.

    ``ops`` picks interpret mode from the default backend, which is the
    CPU here; the kernels are steered to their TPU lowering instead."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    return run


def _assert_kernel(text):
    assert 'custom_call_target="tpu_custom_call"' in text


@pytest.mark.parametrize("C,n,d,K", [
    (8, 4071, 14, 2),   # adult: 32,561 rows over 8 collaborators
    (8, 2000, 16, 26),  # letter: 16,000 rows over 8 collaborators
], ids=["adult", "letter"])
def test_tree_hist_compiles(compile_for, C, n, d, K):
    text = compile_for(
        lambda b, l, w: ops.tree_hist(
            b, l, w, n_leaves=LEAVES, n_bins_p1=N_BINS_P1, use_pallas=True
        ),
        ((C, n, d), jnp.int32), ((C, n), jnp.int32), ((C, n, K), jnp.float32),
    )
    _assert_kernel(text)


@pytest.mark.parametrize("C,H,n", [
    (8, 8, 4071),    # AdaBoost.F, adult, H = C
    (64, 64, 9079),  # C=64 over the forestcover shape at its real row count
], ids=["H8", "H64"])
def test_error_matrix_compiles(compile_for, C, H, n):
    text = compile_for(
        lambda p, y, w: scoring.error_matrix(p, y, w, use_pallas=True),
        ((C, H, n), jnp.int32), ((C, n), jnp.int32), ((C, n), jnp.float32),
    )
    _assert_kernel(text)


def test_weight_update_compiles(compile_for):
    C, n = 8, 4071
    text = compile_for(
        lambda w, m, k, a: scoring.update_weights(w, m, k, a, use_pallas=True),
        ((C, n), jnp.float32), ((C, n), jnp.float32), ((C, n), jnp.float32),
        ((), jnp.float32),
    )
    _assert_kernel(text)


@pytest.mark.parametrize("T,n,K", [
    (100, 16281, 2),  # a 100-member ensemble voting on adult's test split
    (800, 256, 10),   # a large ensemble behind one serving batch
], ids=["T100-adult-test", "T800-B256"])
def test_vote_argmax_compiles(compile_for, T, n, K):
    text = compile_for(
        lambda p, a: ops.vote_argmax(p, a, n_classes=K, use_pallas=True),
        ((T, n), jnp.int32), ((T,), jnp.float32),
    )
    _assert_kernel(text)
