"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode (tests/test_kernels.py) checks the kernels' numbers but not
what the TPU compiler accepts: block shapes must tile by (8, 128) or span
the whole axis, and scratch must fit VMEM.  These tests lower and compile
each kernel of the served path at published widths for one chip of a
``v5e:2x2`` topology that is described, not attached, and check that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).  The
whole decode step is compiled too, to check that it writes each K/V row
in place: no copy of the cache, and no temporaries as large as a layer.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU compiler's library, and the fixture runs
only in the worker that is given this file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.hlo_audit import (
    cache_sized_instructions,
    count_cache_sized_copies,
)
from repro.configs import get_config
from repro.core.hlo_analysis import parse_hlo
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import kv_write as kw
from repro.kernels import ops
from repro.kernels import ssd_scan as ss
from repro.models.model_zoo import ModelBundle
from repro.models.transformer import lm_decode_step

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


# olmo-1b: 16 heads x 128, 8 slots x 2048 cache positions, prefill chunk 32;
# the decode kernels read and write one layer of a 4-layer stacked cache
@pytest.mark.parametrize(
    "n_kv,max_len",
    [(16, 2048), (4, 2048), (16, 600)],
    ids=["mha", "gqa4", "ragged-cache"],
)
def test_flash_decode_compiles(compile_tpu, n_kv, max_len):
    compile_tpu(
        lambda q, k, v, lens, i: da.flash_decode(q, k, v, lens, i),
        ((8, 16, 128), BF16),
        ((4, 8, n_kv, max_len, 128), BF16),
        ((4, 8, n_kv, max_len, 128), BF16),
        ((8,), I32),
        ((), I32),
    )


# 1500 positions end in a partial 8-row tile
@pytest.mark.parametrize(
    "n_kv,max_len", [(16, 2048), (4, 2048), (16, 600), (16, 1500)],
    ids=["mha", "gqa4", "unaligned-ring", "partial-tile"],
)
def test_kv_row_write_compiles_in_place(compile_tpu, n_kv, max_len):
    compiled = compile_tpu(
        lambda k, v, kr, vr, s, i: kw.kv_row_write(k, v, kr, vr, s, i),
        ((4, 8, n_kv, max_len, 128), BF16),
        ((4, 8, n_kv, max_len, 128), BF16),
        ((8, n_kv, 128), BF16),
        ((8, n_kv, 128), BF16),
        ((8,), I32),
        ((), I32),
    )
    # the caches are not donated here, so XLA copies them once to give the
    # kernel buffers it may write; what the kernel itself needs is nothing.
    # A length no row tile divides is laid out position-major by default,
    # so XLA copies it to the kernel's layout and back: the decode step's
    # caches are padded to whole tiles, and only a ring's may end partway.
    if max_len % kw.TILE_ROWS == 0:
        assert compiled.memory_analysis().temp_size_in_bytes < 2**20


# the decode step at published widths, cut to 4 layers: olmo-1b (MHA
# 16x128) at 8 slots and yi-6b (GQA 32:4) at 16, 2048 positions, the cache
# donated.  16 yi-6b slots keep a stacked K or V leaf above VMEM's 128 MiB,
# as at serving sizes: a smaller one XLA may prefetch whole into VMEM for
# the kernel.  A 1500-position limit is served from a cache padded to
# whole row tiles, which the TPU lays out row-major as the kernels need.
@pytest.mark.parametrize(
    "arch,slots,max_len",
    [("olmo-1b", 8, 2048), ("yi-6b", 16, 2048), ("olmo-1b", 8, 1500)],
    ids=["olmo-1b", "yi-6b", "olmo-1b-unaligned-max-len"],
)
def test_decode_step_writes_kv_in_place(
    one_chip, no_persistent_cache, monkeypatch, arch, slots, max_len
):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config(arch), n_layers=4)
    bundle = ModelBundle(cfg)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    params = on_chip(jax.eval_shape(
        lambda: bundle.init_params(jax.random.PRNGKey(0), "bfloat16")
    ))
    caches = on_chip(jax.eval_shape(
        lambda: bundle.init_cache(slots, max_len, "bfloat16")
    ))
    tokens, lengths = on_chip((
        jax.ShapeDtypeStruct((slots, 1), I32),
        jax.ShapeDtypeStruct((slots,), I32),
    ))
    compiled = jax.jit(
        lambda p, t, c, n: lm_decode_step(p, t, c, n, cfg),
        donate_argnums=(2,),  # repro: lint-disable=donate-without-out-shardings
    ).lower(params, tokens, caches, lengths).compile()

    # no buffer of a cache leaf's or a layer's shape besides the donated
    # parameters and the writer's aliased (tuple) result
    text = compiled.as_text()
    comps = parse_hlo(text)
    shapes = {("bf16", a.shape) for a in jax.tree.leaves(caches)}
    found = [
        f"{ins.opcode} %{ins.name} = {ins.type_str}"
        for ins, _ in cache_sized_instructions(comps, shapes)
    ]
    assert not found, found
    assert count_cache_sized_copies(comps, shapes) == 0
    assert "kv_row_write" in text and "flash_decode" in text
    layer_bytes = sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(caches)
    ) // cfg.n_layers
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


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
