"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles.

Sweeps shapes and dtypes per the task contract; every kernel asserts
allclose against ref.py, and the chunked/jnp variants are cross-checked
against brute-force semantics (sequential scan for SSD, full-matrix
attention for the chunked evaluator).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.blocked_matmul import best_tiling, blocked_matmul, traffic_model
from repro.kernels.flash_attention import vmem_footprint_bytes

jax.config.update("jax_platform_name", "cpu")


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(
        atol=3e-5, rtol=1e-5
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,S,D",
    # 200: a length no 128-block divides is padded to 256 and masked
    [(1, 4, 4, 128, 32), (2, 8, 2, 256, 64), (1, 8, 1, 512, 64),
     (1, 4, 2, 200, 32)],
)
@pytest.mark.parametrize(
    "kind,kw",
    [
        ("causal", {}),
        ("sliding", {"window": 64}),
        ("chunked", {"chunk": 128}),
        ("bidirectional", {}),
    ],
)
def test_flash_attention_matches_ref(B, Hq, Hkv, S, D, kind, kw, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    out = ops.attention(q, k, v, kind=kind, backend="pallas", **kw)
    want = ops.attention(q, k, v, kind=kind, backend="ref", **kw)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,Smax,D",
    # 600: the last 512-row tile overhangs the cache
    [(2, 4, 2, 256, 32), (3, 8, 8, 512, 64), (2, 4, 4, 600, 32),
     (1, 8, 1, 128, 64)],
)
def test_flash_decode_matches_ref(B, Hq, Hkv, Smax, D, dtype):
    # the decode step's layer-stacked cache: the kernel reads one layer of
    # it at a scalar-prefetched index, the oracle that layer alone
    L, layer = 3, 1
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    kc = jax.random.normal(ks[1], (L, B, Hkv, Smax, D), dtype)
    vc = jax.random.normal(ks[2], (L, B, Hkv, Smax, D), dtype)
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(1, Smax + 1, size=B), jnp.int32
    )
    out = ops.decode_attention(
        q, kc, vc, lengths, jnp.int32(layer), backend="pallas"
    )
    want = ref.decode_attention(q, kc[layer], vc[layer], lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "lengths,size,layer",
    [
        (list(range(8)), 64, 0),            # slots 0-7 of one tile
        ([63, 63], 64, 2),                  # the last position
        ([5, 70, 133, 64], 64, 1),          # ring wrap: an L cache of 64
        ([0, 17, 40, 9, 63, 31], 64, 3),    # rows of different lengths
        ([3, 26, 11], 12, 1),               # no tile divides the ring
        ([1499, 1496, 7, 1000], 1500, 2),   # rows in a partial last tile
    ],
    ids=["tile-slots", "last-position", "ring-wrap", "mixed-lengths",
         "unaligned-ring", "partial-tile"],
)
def test_kv_row_write_matches_set(lengths, size, layer, dtype):
    L, Hkv, D = 4, 2, 128
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    kc = jax.random.normal(ks[0], (L, B, Hkv, size, D), dtype)
    vc = jax.random.normal(ks[1], (L, B, Hkv, size, D), dtype)
    krow = jax.random.normal(ks[2], (B, Hkv, D), dtype)
    vrow = jax.random.normal(ks[3], (B, Hkv, D), dtype)
    slot = jnp.asarray(lengths, jnp.int32) % size
    got = ops.kv_row_write(
        kc, vc, krow, vrow, slot, jnp.int32(layer), backend="pallas"
    )
    bidx = jnp.arange(B)
    want = (kc.at[layer, bidx, :, slot].set(krow),
            vc.at[layer, bidx, :, slot].set(vrow))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the oracle path of the writer is that same scatter
    ref_path = ops.kv_row_write(
        kc, vc, krow, vrow, slot, jnp.int32(layer), backend="ref"
    )
    for r, w in zip(ref_path, want):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(w))


def test_chunked_attention_matches_full():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, H, S, D = 2, 4, 4096, 32
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    for kind, kw in [("causal", {}), ("sliding", {"window": 512})]:
        full = ref.attention(q, k, v, kind=kind, **kw)
        chunked = ref.attention_chunked(q, k, v, kind=kind, block_q=512, **kw)
        np.testing.assert_allclose(chunked, full, atol=3e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,P,N,chunk", [
    (1, 128, 2, 16, 8, 32), (2, 256, 4, 32, 16, 64), (1, 64, 1, 64, 32, 64),
])
def test_ssd_scan_pallas_and_chunked_vs_sequential(B, T, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = (jax.random.normal(ks[0], (B, T, H, P)) * 0.5).astype(dtype)
    dt = (jax.nn.softplus(jax.random.normal(ks[1], (B, T, H))) * 0.1)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = (jax.random.normal(ks[3], (B, T, N)) * 0.5)
    Cm = (jax.random.normal(ks[4], (B, T, N)) * 0.5)
    want = ref.ssd_scan_sequential(x, dt, A, Bm, Cm)
    chk = ref.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    pls = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, backend="pallas")
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(
        atol=2e-4, rtol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(chk, np.float32), np.asarray(want, np.float32), **tol
    )
    np.testing.assert_allclose(
        np.asarray(pls, np.float32), np.asarray(want, np.float32), **tol
    )


def test_ssd_prefill_state_matches_decode_continuation():
    """State handoff: scan T tokens, then decode-step one more ==
    scanning T+1 tokens."""
    B, T, H, P, N = 1, 64, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (B, T + 1, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T + 1, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T + 1, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, T + 1, N)) * 0.5
    y_full = ref.ssd_scan_sequential(x, dt, A, Bm, Cm)
    _, state = ref.ssd_scan(
        x[:, :T], dt[:, :T], A, Bm[:, :T], Cm[:, :T],
        chunk=32, return_state=True,
    )
    y_last, _ = ref.ssd_decode_step(
        x[:, T], dt[:, T], A, Bm[:, T], Cm[:, T], state
    )
    np.testing.assert_allclose(y_last, y_full[:, T], atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,N,K,bm,bn,bk", [
    (256, 128, 512, 128, 128, 128),
    (128, 128, 128, 128, 128, 128),
    (512, 256, 256, 256, 128, 256),
])
def test_blocked_matmul(M, N, K, bm, bn, bk, dtype):
    a = jax.random.normal(jax.random.PRNGKey(5), (M, K), dtype)
    b = jax.random.normal(jax.random.PRNGKey(6), (K, N), dtype)
    out = blocked_matmul(
        a, b, bm=bm, bn=bn, bk=bk, out_dtype=jnp.float32, interpret=True
    )
    want = jnp.dot(
        a.astype(jnp.float32), b.astype(jnp.float32)
    )
    tol = dict(atol=1.5, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=1e-3, rtol=1e-5
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **tol)


def test_pallas_attention_grads_match_ref():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, H, S, D = 1, 4, 128, 32
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))

    def loss(backend):
        return lambda q, k, v: jnp.sum(
            ops.attention(q, k, v, kind="causal", backend=backend) ** 2
        )

    g_p = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss("ref"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_p, g_r):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_matmul_traffic_model_and_tiling():
    t = traffic_model(1024, 1024, 1024, 256, 256, 256)
    # each A byte read N/bn=4 times etc.
    assert t["hbm_bytes"] == (1024 * 1024 * 4 * 2 + 1024 * 1024) * 2
    bm, bn, bk = best_tiling(4096, 4096, 4096)
    assert 4096 % bm == 0 and 4096 % bn == 0 and 4096 % bk == 0
    big = traffic_model(4096, 4096, 4096, bm, bn, bk)
    small = traffic_model(4096, 4096, 4096, 128, 128, 128)
    assert big["arithmetic_intensity"] >= small["arithmetic_intensity"]
    assert vmem_footprint_bytes(128, 128, 64) < 16 * 2**20
