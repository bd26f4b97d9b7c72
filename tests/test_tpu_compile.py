"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode (tests/test_kernels.py) checks the kernels' numbers but not
what the TPU compiler accepts: block shapes must tile by (8, 128) or span
the whole axis, and scratch must fit VMEM.  These tests lower and compile
each kernel of the served path at published widths for one chip of a
``v5e:2x2`` topology that is described, not attached, and check that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU compiler's library, and the fixture runs
only in the worker that is given this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import ssd_scan as ss

BF16 = jnp.bfloat16
I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compile_tpu(one_chip, no_persistent_cache):
    def run(fn, *shapes):
        args = [
            jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes
        ]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    return run


# olmo-1b: 16 heads x 128, 8 slots x 2048 cache positions, prefill chunk 32
@pytest.mark.parametrize(
    "n_kv,max_len",
    [(16, 2048), (4, 2048), (16, 600)],
    ids=["mha", "gqa4", "ragged-cache"],
)
def test_flash_decode_compiles(compile_tpu, n_kv, max_len):
    compile_tpu(
        lambda q, k, v, lens: da.flash_decode(q, k, v, lens),
        ((8, 16, 128), BF16),
        ((8, n_kv, max_len, 128), BF16),
        ((8, n_kv, max_len, 128), BF16),
        ((8,), I32),
    )


@pytest.mark.parametrize("kind", ["causal", "bidirectional"])
def test_flash_attention_compiles(compile_tpu, kind):
    compile_tpu(
        lambda q, k, v: fa.flash_attention(q, k, v, kind=kind),
        ((1, 16, 2048, 128), BF16),
        ((1, 16, 2048, 128), BF16),
        ((1, 16, 2048, 128), BF16),
    )


def test_flash_attention_compiles_at_unaligned_length(compile_tpu):
    # a prompt length no 128-block divides: padded to 2048, not one
    # whole-axis (2000, 2000) score tile, which would not fit VMEM
    compile_tpu(
        lambda q, k, v: fa.flash_attention(q, k, v, kind="causal"),
        ((1, 16, 2000, 128), BF16),
        ((1, 16, 2000, 128), BF16),
        ((1, 16, 2000, 128), BF16),
    )


def test_flash_prefill_compiles_at_batch_8(compile_tpu):
    sk = 2048 + 32        # prior cache ++ one prefill chunk
    compile_tpu(
        lambda q, k, v, qp, kp: fa.flash_prefill(q, k, v, qp, kp),
        ((8, 16, 32, 128), BF16),
        ((8, 16, sk, 128), BF16),
        ((8, 16, sk, 128), BF16),
        ((8, 32), I32),
        ((8, sk), I32),
    )


def test_ssd_scan_compiles_at_mamba2_780m_widths(compile_tpu):
    # d_inner 3072 / head_dim 64 -> 48 heads, d_state 128, 2048 tokens
    compile_tpu(
        lambda x, dt, a, b, c: ss.ssd_scan(x, dt, a, b, c, chunk=64),
        ((1, 2048, 48, 64), BF16),
        ((1, 2048, 48), jnp.float32),
        ((48,), jnp.float32),
        ((1, 2048, 128), BF16),
        ((1, 2048, 128), BF16),
    )
