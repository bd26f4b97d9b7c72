"""Public jit'd wrappers over the Pallas kernels with ref dispatch.

The model zoo calls these.  The platform picks the path: on TPU the
compiled Pallas kernels run, on every other platform the pure-jnp
oracles of ``ref.py`` do.  ``backend='pallas'`` / ``backend='ref'``
forces one side; a forced Pallas call off the TPU runs the kernel in
interpret mode, which is how the tests sweep both and assert allclose.

Training gradients: when the Pallas forward is selected, attention ops are
wrapped in ``jax.custom_vjp`` whose backward *recomputes* with the oracle —
numerically exact, flash-style-memory only in forward.  (A Pallas backward
kernel is a further optimization.)
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import blocked_matmul as _bm
from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import kv_write as _kw
from repro.kernels import ref as _ref

Backend = Literal["ref", "pallas"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(backend: Backend | None) -> Backend:
    """``backend`` when forced, else the platform's path."""
    if backend is not None:
        return backend
    return "pallas" if _on_tpu() else "ref"


def _split_axes(batch: int, heads: int, heads_rule: str, kv_cache: bool):
    """Mesh axes a per-device kernel call splits its batch and head dims
    over, laid out as the placement lays out a KV cache
    (``defs_to_specs``): the rule table's ``batch`` and ``heads_rule``
    axes, then, for a call that reads a peer/remote-tier cache, the
    cache's donor axes on the first of the two dims they divide.
    ``heads`` is the KV head count: q heads split by the same factor keep
    each device's query groups beside their KV heads."""
    from repro.models.sharding import (
        current_kv_donor_axes,
        current_mesh,
        donor_extend,
        spec_for,
    )

    mesh = current_mesh()
    logical = ("batch", heads_rule)
    spec = spec_for((batch, heads), logical, mesh)
    donor = current_kv_donor_axes() if kv_cache else ()
    if donor:
        spec = donor_extend(spec, (batch, heads), mesh, donor, logical)
    b, h = (*spec, None, None)[:2]
    return {"b": b, "h": h, None: None}


def _per_shard(fn, *args, dims, out_dims, heads, heads_rule="kv_heads",
               kv_cache=False):
    """``fn(*args)``, run per device of the active multi-device mesh.

    XLA cannot partition a Mosaic kernel, so on a mesh of several devices
    the call goes through ``shard_map``.  ``dims`` tags each argument's
    dimensions (and ``out_dims`` the output's) ``"b"`` for batch, ``"h"``
    for heads or None; tagged dims split over :func:`_split_axes`, the
    rest arrive whole.  Attention and the SSD scan are independent per
    batch row and per head, so no device needs another's slice.
    """
    from repro.models.sharding import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return fn(*args)
    split = _split_axes(args[0].shape[0], heads, heads_rule, kv_cache)

    def spec(tags):
        return P(*(split[t] for t in tags))

    out_specs = (
        tuple(spec(d) for d in out_dims) if isinstance(out_dims[0], tuple)
        else spec(out_dims)
    )
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(spec(d) for d in dims), out_specs=out_specs,
        check_vma=False,
    )(*args)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

_ATTN = ("b", "h", None, None)     # (B, H, S, D)
_STACK = (None, "b", "h", None, None)   # (layers, B, Hkv, S, D)
_SSD = ("b", None, "h", None)      # (B, T, H, P)

@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _pallas_attention(q, k, v, kind, window, chunk, scale, q_offset):
    return _fa.flash_attention(
        q, k, v, kind=kind, window=window, chunk=chunk,
        scale=scale, q_offset=q_offset, interpret=not _on_tpu(),
    )


def _pallas_attention_fwd(q, k, v, kind, window, chunk, scale, q_offset):
    out = _pallas_attention(q, k, v, kind, window, chunk, scale, q_offset)
    return out, (q, k, v)


def _pallas_attention_bwd(kind, window, chunk, scale, q_offset, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref.attention(
            q_, k_, v_, kind=kind, window=window, chunk=chunk,
            scale=scale, q_offset=q_offset,
        ),
        q, k, v,
    )
    return vjp(g)


_pallas_attention.defvjp(_pallas_attention_fwd, _pallas_attention_bwd)


def attention(
    q, k, v, *,
    kind: str = "causal",
    window: int = 0,
    chunk: int = 0,
    scale: float | None = None,
    q_offset: int = 0,
    k_lengths=None,
    backend: Backend | None = None,
):
    """(B, Hq, Sq, D) x (B, Hkv, Sk, D) GQA attention with mask kinds."""
    if _resolve(backend) == "pallas" and k_lengths is None:
        return _per_shard(
            lambda q, k, v: _pallas_attention(
                q, k, v, kind, window, chunk, scale, q_offset
            ),
            q, k, v, dims=(_ATTN,) * 3, out_dims=_ATTN, heads=k.shape[1],
        )
    if k_lengths is None and q.shape[2] >= 2048:
        # long sequences: flash-style chunked evaluation (memory O(S·bq))
        return _ref.attention_chunked(
            q, k, v, kind=kind, window=window, chunk=chunk,
            scale=scale, q_offset=q_offset,
        )
    return _ref.attention(
        q, k, v, kind=kind, window=window, chunk=chunk,
        scale=scale, q_offset=q_offset, k_lengths=k_lengths,
    )


def decode_attention(
    q, k_cache, v_cache, lengths, layer, *,
    scale: float | None = None,
    backend: Backend | None = None,
):
    """(B, Hq, D) single-token decode against layer ``layer`` of a
    stacked (L, B, Hkv, S, D) KV cache."""
    if _resolve(backend) == "pallas":
        return _per_shard(
            lambda q, k, v, lens, i: _da.flash_decode(
                q, k, v, lens, i, scale=scale, interpret=not _on_tpu()
            ),
            q, k_cache, v_cache, lengths, layer,
            dims=(("b", "h", None), _STACK, _STACK, ("b",), ()),
            out_dims=("b", "h", None), heads=k_cache.shape[2], kv_cache=True,
        )
    return _ref.decode_attention(
        q,
        jax.lax.dynamic_index_in_dim(k_cache, layer, keepdims=False),
        jax.lax.dynamic_index_in_dim(v_cache, layer, keepdims=False),
        lengths, scale=scale,
    )


def kv_row_write(
    k_cache, v_cache, k_row, v_row, slot, layer, *,
    backend: Backend | None = None,
):
    """Write each row's new K/V (B, Hkv, D) at ``[layer, b, :, slot[b]]``
    of stacked (L, B, Hkv, S, D) caches; returns the updated caches.
    The Pallas writer updates the buffers in place."""
    if _resolve(backend) == "pallas":
        return _per_shard(
            lambda kr, vr, s, i, k, v: _kw.kv_row_write(
                k, v, kr, vr, s, i, interpret=not _on_tpu()
            ),
            k_row, v_row, slot, layer, k_cache, v_cache,
            dims=(("b", "h", None), ("b", "h", None), ("b",), (),
                  _STACK, _STACK),
            out_dims=(_STACK, _STACK), heads=k_cache.shape[2],
            kv_cache=True,
        )
    # per (row, head) indices: each update is one contiguous D-vector, so
    # the cache keeps its row-major layout (a window over heads and D
    # makes XLA lay the cache out position-major and relayout it)
    bidx = jnp.arange(k_cache.shape[1])[:, None]
    hidx = jnp.arange(k_cache.shape[2])[None, :]
    sidx = slot[:, None]
    return (
        k_cache.at[layer, bidx, hidx, sidx].set(k_row.astype(k_cache.dtype)),
        v_cache.at[layer, bidx, hidx, sidx].set(v_row.astype(v_cache.dtype)),
    )


def prefill_attention(
    q, k, v, q_pos, k_pos, *,
    kind: str = "causal",
    window: int = 0,
    chunk: int = 0,
    scale: float | None = None,
    backend: Backend | None = None,
):
    """(B, Hq, Sq, D) chunk queries vs (B, Hkv, Sk, D) [cache ++ chunk] keys.

    Position-tensor masked attention for the serving engine's chunked
    batched prefill: causal within the chunk, full (windowed / chunk-local)
    against the prior cache, ``k_pos < 0`` slots masked out.  Inference
    only — no VJP is registered for the Pallas path.
    """
    if _resolve(backend) == "pallas":
        return _per_shard(
            lambda q, k, v, qp, kp: _fa.flash_prefill(
                q, k, v, qp, kp, kind=kind, window=window, chunk=chunk,
                scale=scale, interpret=not _on_tpu(),
            ),
            q, k, v, q_pos, k_pos,
            dims=(_ATTN,) * 3 + (("b", None),) * 2, out_dims=_ATTN,
            heads=k.shape[1], kv_cache=True,
        )
    return _ref.prefill_attention(
        q, k, v, q_pos, k_pos,
        kind=kind, window=window, chunk=chunk, scale=scale,
    )


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _pallas_ssd(x, dt, A, Bmat, Cmat, chunk):
    return _ssd_pallas_fwd_only(x, dt, A, Bmat, Cmat, chunk)


def _ssd_pallas_fwd_only(x, dt, A, Bmat, Cmat, chunk):
    from repro.kernels.ssd_scan import ssd_scan as _k

    return _k(x, dt, A, Bmat, Cmat, chunk=chunk, interpret=not _on_tpu())


def _pallas_ssd_fwd(x, dt, A, Bmat, Cmat, chunk):
    return _pallas_ssd(x, dt, A, Bmat, Cmat, chunk), (x, dt, A, Bmat, Cmat)


def _pallas_ssd_bwd(chunk, res, g):
    x, dt, A, Bmat, Cmat = res
    _, vjp = jax.vjp(
        lambda *a: _ref.ssd_scan(*a, chunk=chunk), x, dt, A, Bmat, Cmat
    )
    return vjp(g)


_pallas_ssd.defvjp(_pallas_ssd_fwd, _pallas_ssd_bwd)


def ssd_scan(
    x, dt, A, Bmat, Cmat, *,
    chunk: int = 64,
    init_state=None,
    return_state: bool = False,
    backend: Backend | None = None,
):
    if (
        _resolve(backend) == "pallas"
        and init_state is None
        and not return_state
    ):
        return _per_shard(
            lambda x, dt, A, Bm, Cm: _pallas_ssd(x, dt, A, Bm, Cm, chunk),
            x, dt, A, Bmat, Cmat,
            dims=(_SSD, ("b", None, "h"), ("h",), ("b",), ("b",)),
            out_dims=_SSD, heads=x.shape[2], heads_rule="ssm_heads",
        )
    return _ref.ssd_scan(
        x, dt, A, Bmat, Cmat, chunk=chunk,
        init_state=init_state, return_state=return_state,
    )


def ssd_decode_step(x, dt, A, Bvec, Cvec, state):
    return _ref.ssd_decode_step(x, dt, A, Bvec, Cvec, state)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

def matmul(
    a, b, *,
    out_dtype=None,
    bm: int = _bm.DEFAULT_BM,
    bn: int = _bm.DEFAULT_BN,
    bk: int = _bm.DEFAULT_BK,
    backend: Backend | None = None,
):
    if _resolve(backend) == "pallas":
        return _bm.blocked_matmul(
            a, b, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
            interpret=not _on_tpu(),
        )
    return _ref.matmul(a, b, out_dtype=out_dtype)
