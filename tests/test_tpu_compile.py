"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e (no chip needed): the TPU compiler refuses illegal block shapes
that interpret mode never sees.  It does not check a kernel's VMEM use
(a block far past the chip's VMEM compiles here); the tiles' VMEM is
held by ``tests/test_kernels.py``.  Each kernel is
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

    ``ops`` dispatches on the default backend, which is the CPU here;
    the kernels are steered to their TPU dispatch and lowering instead."""
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    return run


def _assert_kernel(text):
    assert 'custom_call_target="tpu_custom_call"' in text


@pytest.mark.parametrize("batch,n,d,K,leaves,flag", [
    ((8,), 4071, 14, 2, LEAVES, {"use_pallas": True}),   # adult: 32,561 rows over 8 collaborators
    ((8,), 2000, 16, 26, LEAVES, {"use_pallas": True}),  # letter: 16,000 rows over 8 collaborators
    ((64,), 7262, 54, 2, LEAVES, {}),  # covtype_c64: 464,809 rows over 64, default dispatch
    ((), 116202, 54, 2, LEAVES, {}),   # covtype_c4: one chip's 2-D shard, default dispatch
    ((8,), 4071, 14, 2, 128, {}),  # adult at depth 8: its deepest level, default dispatch
    ((8,), 2000, 16, 26, 32, {}),  # letter at depth 6: its deepest level, default dispatch
], ids=["adult", "letter", "covtype_c64", "covtype_c4_shard", "adult_depth8", "letter_depth6"])
def test_tree_hist_compiles(compile_for, batch, n, d, K, leaves, flag):
    text = compile_for(
        lambda b, l, w: ops.tree_hist(b, l, w, n_leaves=leaves, n_bins_p1=N_BINS_P1, **flag),
        (batch + (n, d), jnp.int32), (batch + (n,), jnp.int32), (batch + (n, K), jnp.float32),
    )
    _assert_kernel(text)


def test_tree_hist_tile_overrides_compile(compile_for):
    """``tree_block_s`` / ``tree_block_d`` overrides off the lane and
    sublane multiples are rounded up to them, so they still lower."""
    text = compile_for(
        lambda b, l, w: ops.tree_hist(b, l, w, n_leaves=LEAVES, n_bins_p1=N_BINS_P1,
                                      block_s=200, block_d=4),
        ((8, 4071, 14), jnp.int32), ((8, 4071), jnp.int32), ((8, 4071, 2), jnp.float32),
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
