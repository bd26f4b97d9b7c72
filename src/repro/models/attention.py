"""Attention blocks: GQA (+sliding/chunked/global variants) and MLA.

Three execution paths per block, matching the assigned shapes:

* ``train``    — full-sequence causal/windowed attention, differentiable;
* ``prefill``  — same math, also materializes the KV cache;
* ``decode``   — one token against the cache (flash-decode datapath);
  GQA decode takes the layer-stacked cache and a layer index, writes its
  new row there in place and reads the same buffer.

MLA (DeepSeek-V2) caches the 512-dim latent + shared rope key and uses the
**absorbed** decode formulation (q absorbed through W_uk, output through
W_uv) so decode reads scale with kv_lora, not heads — the architecture-level
version of the paper's "shrink what you must stream" lesson.

Sliding-window layers keep a **ring-buffer cache of size window** (order
does not matter to softmax; masking handles validity) — ``long_500k``
memory for gemma3 local layers is O(window), not O(seq).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import AttentionSpec
from repro.kernels import ops
from repro.kernels.kv_write import TILE_ROWS
from repro.models.layers import rope
from repro.models.sharding import Param, shard


# ---------------------------------------------------------------------------
# Param defs
# ---------------------------------------------------------------------------

def attention_defs(d_model: int, spec: AttentionSpec) -> dict:
    if spec.kind == "mla":
        qk_head = spec.nope_head_dim + spec.rope_head_dim
        defs = {
            "w_kv_a": Param((d_model, spec.kv_lora), ("embed", "lora")),
            "w_k_rope": Param((d_model, spec.rope_head_dim), ("embed", None)),
            "w_k_b": Param(
                (spec.kv_lora, spec.n_heads, spec.nope_head_dim),
                ("lora", "heads", "head_dim"),
            ),
            "w_v_b": Param(
                (spec.kv_lora, spec.n_heads, spec.v_head_dim),
                ("lora", "heads", "head_dim"),
            ),
            "w_o": Param(
                (spec.n_heads, spec.v_head_dim, d_model),
                ("heads", "head_dim", "embed"),
            ),
        }
        if spec.q_lora:
            defs["w_q_a"] = Param((d_model, spec.q_lora), ("embed", "lora"))
            defs["w_q_b"] = Param(
                (spec.q_lora, spec.n_heads, qk_head),
                ("lora", "heads", "head_dim"),
            )
        else:
            defs["w_q"] = Param(
                (d_model, spec.n_heads, qk_head),
                ("embed", "heads", "head_dim"),
            )
        return defs

    defs = {
        "w_q": Param(
            (d_model, spec.n_heads, spec.d_head),
            ("embed", "heads", "head_dim"),
        ),
        "w_k": Param(
            (d_model, spec.n_kv_heads, spec.d_head),
            ("embed", "kv_heads", "head_dim"),
        ),
        "w_v": Param(
            (d_model, spec.n_kv_heads, spec.d_head),
            ("embed", "kv_heads", "head_dim"),
        ),
        "w_o": Param(
            (spec.n_heads, spec.d_head, d_model),
            ("heads", "head_dim", "embed"),
        ),
    }
    if spec.qk_norm:
        defs["q_norm"] = Param((spec.d_head,), (None,), init="ones")
        defs["k_norm"] = Param((spec.d_head,), (None,), init="ones")
    return defs


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _mask_kind(code: str) -> str:
    return {"F": "causal", "G": "causal", "L": "sliding", "C": "chunked",
            "X": "bidirectional"}[code]


def _theta(spec: AttentionSpec, code: str) -> float:
    if code == "G" and spec.rope_theta_global:
        return spec.rope_theta_global
    return spec.rope_theta


# ---------------------------------------------------------------------------
# Cache defs
# ---------------------------------------------------------------------------

def cache_defs(
    batch: int, max_len: int, spec: AttentionSpec, code: str = "F"
) -> dict:
    """Per-layer decode-cache defs (Param reused as a shaped placeholder)."""
    if spec.kind == "mla":
        return {
            "ckv": Param(
                (batch, max_len, spec.kv_lora),
                ("batch", "kv_seq", "lora"), init="zeros",
            ),
            "krope": Param(
                (batch, max_len, spec.rope_head_dim),
                ("batch", "kv_seq", None), init="zeros",
            ),
        }
    size = min(max_len, spec.window) if code == "L" and spec.window else max_len
    if code == "C" and spec.chunk:
        size = min(max_len, 2 * spec.chunk)  # ring over current+prev chunk
    if size == max_len:
        # a cache no ring wraps ends on a whole row tile: the TPU lays out
        # a (.., size, D) array whose size no tile divides position-major,
        # and the decode kernels would copy it back to row-major each step
        size = -(-max_len // TILE_ROWS) * TILE_ROWS
    return {
        "k": Param(
            (batch, spec.n_kv_heads, size, spec.d_head),
            ("batch", "kv_heads", "kv_seq", "head_dim"), init="zeros",
        ),
        "v": Param(
            (batch, spec.n_kv_heads, size, spec.d_head),
            ("batch", "kv_heads", "kv_seq", "head_dim"), init="zeros",
        ),
    }


# ---------------------------------------------------------------------------
# Apply: GQA
# ---------------------------------------------------------------------------

def _gqa_project(params, x, spec, positions, code):
    q = jnp.einsum("bsd,dhk->bhsk", x, params["w_q"])
    k = jnp.einsum("bsd,dhk->bhsk", x, params["w_k"])
    v = jnp.einsum("bsd,dhk->bhsk", x, params["w_v"])
    if spec.qk_norm:
        q = _rms(q, params["q_norm"])
        k = _rms(k, params["k_norm"])
    th = _theta(spec, code)
    q = rope(q, positions, th)
    k = rope(k, positions, th)
    # NOTE: deliberately not "seq"-sharded here.  Under sequence-parallel
    # rules x is seq-sharded at layer boundaries; attention needs the full
    # sequence per head, so q/k/v carry head sharding only — the implied
    # reshard is the Megatron-SP all-gather.  Seq-sharding KV when
    # kv_heads < TP degree trips XLA involuntary full rematerialization
    # against the q-chunked attention loop (observed on yi-6b: 16 GiB
    # replication copies in the backward).
    q = shard(q, "batch", "heads", None, "head_dim")
    k = shard(k, "batch", "kv_heads", None, "head_dim")
    v = shard(v, "batch", "kv_heads", None, "head_dim")
    return q, k, v


def gqa_train(params, x, spec: AttentionSpec, code: str):
    """Full-sequence attention; x (B,S,D)."""
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    q, k, v = _gqa_project(params, x, spec, positions, code)
    o = ops.attention(
        q, k, v,
        kind=_mask_kind(code), window=spec.window, chunk=spec.chunk,
    )
    out = jnp.einsum("bhsk,hkd->bsd", o, params["w_o"])
    return shard(out, "batch", "seq", "embed")


def _ring_positions(offsets: jax.Array, size: int) -> jax.Array:
    """Absolute position held by each ring slot *before* a chunk append.

    ``offsets`` (B,) is each row's cache fill.  Slot ``r`` holds the
    largest position ``p ≡ r (mod size)`` with ``p < offsets``; a negative
    result marks a hole (never-written slot).  For non-ring caches
    (``size >= max_len``) this degenerates to ``p = r`` for ``r < offsets``.
    """
    r = jnp.arange(size, dtype=jnp.int32)[None, :]
    return r + size * jnp.floor_divide(offsets[:, None] - 1 - r, size)


def _append_kv(cache, k_new, v_new, offsets, new_lens):
    """Offset-aware KV append: scatter chunk keys into each row's ring.

    Row ``b`` writes positions ``[offsets[b], offsets[b] + new_lens[b])``
    at ring slots ``pos % size``.  Chunk entries past ``new_lens`` — and,
    when the chunk is longer than the ring, entries the chunk itself would
    immediately overwrite — are routed to an out-of-bounds slot and
    dropped, so rows with ``new_lens == 0`` keep their cache bit-for-bit.
    """
    size = cache["k"].shape[2]
    B, _, S, _ = k_new.shape
    j = jnp.arange(S, dtype=jnp.int32)[None, :]
    keep = (j < new_lens[:, None]) & (j >= new_lens[:, None] - size)
    slot = jnp.where(keep, (offsets[:, None] + j) % size, size)
    bidx = jnp.arange(B)[:, None]
    return {
        "k": cache["k"].at[bidx, :, slot].set(
            k_new.transpose(0, 2, 1, 3).astype(cache["k"].dtype),
            mode="drop",
        ),
        "v": cache["v"].at[bidx, :, slot].set(
            v_new.transpose(0, 2, 1, 3).astype(cache["v"].dtype),
            mode="drop",
        ),
    }


def gqa_prefill(params, x, cache, spec: AttentionSpec, code: str):
    """Train-path attention + cache fill. Returns (out, cache)."""
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    q, k, v = _gqa_project(params, x, spec, positions, code)
    o = ops.attention(
        q, k, v,
        kind=_mask_kind(code), window=spec.window, chunk=spec.chunk,
    )
    zeros = jnp.zeros((B,), jnp.int32)
    cache = _append_kv(cache, k, v, zeros, zeros + S)
    out = jnp.einsum("bhsk,hkd->bsd", o, params["w_o"])
    return shard(out, "batch", "seq", "embed"), cache


def gqa_prefill_at(
    params, x, cache, offsets, new_lens, spec: AttentionSpec, code: str
):
    """Offset-aware chunk prefill: continue each row's cache in one pass.

    ``x`` (B, S, D) holds one prefill chunk; row ``b`` appends
    ``new_lens[b] <= S`` tokens at positions ``offsets[b]..``.  Queries
    attend causally within the chunk and fully (windowed / chunk-locally,
    by absolute position) against the prior cache — token-by-token decode
    replay semantics in a single dispatch, reading the prior cache once
    per chunk instead of once per token.  Rows with ``new_lens == 0`` are
    untouched.  Keys are compared in the cache's storage dtype so logits
    and cache match the decode replay exactly.
    """
    B, S, _ = x.shape
    offsets = offsets.astype(jnp.int32)
    new_lens = new_lens.astype(jnp.int32)
    positions = offsets[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    q, k, v = _gqa_project(params, x, spec, positions[:, None, :], code)

    size = cache["k"].shape[2]
    j = jnp.arange(S, dtype=jnp.int32)[None, :]
    kpos_new = jnp.where(j < new_lens[:, None], positions, -1)
    kpos = jnp.concatenate([_ring_positions(offsets, size), kpos_new], axis=1)
    kcat = jnp.concatenate(
        [cache["k"], k.astype(cache["k"].dtype)], axis=2
    )
    vcat = jnp.concatenate(
        [cache["v"], v.astype(cache["v"].dtype)], axis=2
    )
    o = ops.prefill_attention(
        q, kcat, vcat, positions, kpos,
        kind=_mask_kind(code), window=spec.window, chunk=spec.chunk,
    )
    cache = _append_kv(cache, k, v, offsets, new_lens)
    out = jnp.einsum("bhsk,hkd->bsd", o, params["w_o"])
    return shard(out, "batch", "seq", "embed"), cache


def gqa_decode_at(
    params, x, cache, layer, lengths, spec: AttentionSpec, code: str
):
    """One-token decode against layer ``layer`` of a stacked cache.

    ``x`` (B,1,D); ``lengths`` (B,) tokens already cached; ``cache``
    holds (L, B, Hkv, size, D) K and V.  The new row is written in place
    (:func:`repro.kernels.ops.kv_row_write`, at ring slot
    ``lengths % size``) and attention reads the same stacked buffer at
    ``layer``: no layer slice of the cache is materialised.
    """
    positions = lengths[:, None, None]           # (B,1,1) for (B,H,1,dh)
    q = jnp.einsum("bsd,dhk->bhsk", x, params["w_q"])
    k = jnp.einsum("bsd,dhk->bhsk", x, params["w_k"])
    v = jnp.einsum("bsd,dhk->bhsk", x, params["w_v"])
    if spec.qk_norm:
        q = _rms(q, params["q_norm"])
        k = _rms(k, params["k_norm"])
    th = _theta(spec, code)
    q = rope(q, positions, th)[:, :, 0]          # (B,H,D)
    k = rope(k, positions, th)[:, :, 0]          # (B,Hkv,D)
    v = v[:, :, 0]

    size = cache["k"].shape[3]
    slot = (lengths % size).astype(jnp.int32)    # ring index
    k_cache, v_cache = ops.kv_row_write(
        cache["k"], cache["v"], k, v, slot, layer
    )

    if code == "L" and spec.window:
        valid = jnp.minimum(lengths + 1, size)
    elif code == "C" and spec.chunk:
        # entries in the current chunk (ring holds 2 chunks; mask the rest)
        valid = (lengths % spec.chunk) + 1
        # ring layout: we mask by recency -> approximate with ring validity
        valid = jnp.minimum(valid, size)
    else:
        valid = jnp.minimum(lengths + 1, size)
    o = ops.decode_attention(q, k_cache, v_cache, valid, layer)
    out = jnp.einsum("bhk,hkd->bd", o, params["w_o"])[:, None]
    return shard(out, "batch", "seq", "embed"), {"k": k_cache, "v": v_cache}


def gqa_decode(params, x, cache, lengths, spec: AttentionSpec, code: str):
    """:func:`gqa_decode_at` on a one-layer (B, Hkv, size, D) cache, as
    the encoder-decoder's layer scan slices it."""
    out, c = gqa_decode_at(
        params, x, jax.tree.map(lambda a: a[None], cache), jnp.int32(0),
        lengths, spec, code,
    )
    return out, jax.tree.map(lambda a: a[0], c)


# ---------------------------------------------------------------------------
# Apply: MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------

def _mla_q(params, x, spec, positions):
    if "w_q_a" in params:
        qa = jnp.einsum("bsd,dr->bsr", x, params["w_q_a"])
        q = jnp.einsum("bsr,rhk->bhsk", qa, params["w_q_b"])
    else:
        q = jnp.einsum("bsd,dhk->bhsk", x, params["w_q"])
    qn = q[..., : spec.nope_head_dim]
    qr = rope(q[..., spec.nope_head_dim:], positions, spec.rope_theta)
    return qn, qr


def mla_train(params, x, spec: AttentionSpec, code: str = "F"):
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    qn, qr = _mla_q(params, x, spec, positions)
    ckv = jnp.einsum("bsd,dr->bsr", x, params["w_kv_a"])
    kr = rope(
        jnp.einsum("bsd,dk->bsk", x, params["w_k_rope"])[:, None],
        positions, spec.rope_theta,
    )                                             # (B,1,S,rope)
    kn = jnp.einsum("bsr,rhk->bhsk", ckv, params["w_k_b"])
    v = jnp.einsum("bsr,rhk->bhsk", ckv, params["w_v_b"])
    q = jnp.concatenate([qn, qr], -1)
    k = jnp.concatenate(
        [kn, jnp.broadcast_to(kr, (*kn.shape[:-1], spec.rope_head_dim))], -1
    )
    q = shard(q, "batch", "heads", "seq", "head_dim")
    k = shard(k, "batch", "heads", "seq", "head_dim")
    v = shard(v, "batch", "heads", "seq", "head_dim")
    o = ops.attention(q, k, v, kind="causal")
    out = jnp.einsum("bhsk,hkd->bsd", o, params["w_o"])
    return shard(out, "batch", "seq", "embed")


def _append_latent(cache, ckv_new, kr_new, offsets, new_lens):
    """Offset-aware MLA latent append (non-ring: slot == position).

    Row ``b`` writes ``new_lens[b]`` latents at slots ``offsets[b]..``;
    entries past ``new_lens`` go to an out-of-bounds slot and are dropped.
    """
    Smax = cache["ckv"].shape[1]
    B, S, _ = ckv_new.shape
    j = jnp.arange(S, dtype=jnp.int32)[None, :]
    idx = jnp.where(
        j < new_lens[:, None],
        jnp.minimum(offsets[:, None] + j, Smax - 1),
        Smax,
    )
    bidx = jnp.arange(B)[:, None]
    return {
        "ckv": cache["ckv"].at[bidx, idx].set(
            ckv_new.astype(cache["ckv"].dtype), mode="drop"),
        "krope": cache["krope"].at[bidx, idx].set(
            kr_new.astype(cache["krope"].dtype), mode="drop"),
    }


def mla_prefill(params, x, cache, spec: AttentionSpec, code: str = "F"):
    B, S, _ = x.shape
    out = mla_train(params, x, spec, code)
    positions = jnp.arange(S)[None, :]
    ckv = jnp.einsum("bsd,dr->bsr", x, params["w_kv_a"])
    kr = rope(
        jnp.einsum("bsd,dk->bsk", x, params["w_k_rope"]),
        positions, spec.rope_theta,
    )
    zeros = jnp.zeros((B,), jnp.int32)
    cache = _append_latent(cache, ckv, kr, zeros, zeros + S)
    return out, cache


def mla_prefill_at(
    params, x, cache, offsets, new_lens, spec: AttentionSpec, code: str = "F"
):
    """Offset-aware absorbed-MLA chunk prefill (decode-replay semantics).

    Latents are scattered first (the cache is non-ring, slot == position),
    then the chunk's queries run the absorbed decode formulation against
    the updated cache with a causal mask on absolute positions — the same
    score layout, dtype path, and summation order as ``mla_decode``, so a
    chunked prefill reproduces the token-by-token replay exactly.
    """
    B, S, _ = x.shape
    offsets = offsets.astype(jnp.int32)
    new_lens = new_lens.astype(jnp.int32)
    positions = offsets[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    qn, qr = _mla_q(params, x, spec, positions[:, None, :])   # (B,H,S,*)

    ckv_new = jnp.einsum("bsd,dr->bsr", x, params["w_kv_a"])
    kr_new = rope(
        jnp.einsum("bsd,dk->bsk", x, params["w_k_rope"]),
        positions, spec.rope_theta,
    )
    cache = _append_latent(cache, ckv_new, kr_new, offsets, new_lens)
    ckv, kr = cache["ckv"], cache["krope"]
    Smax = ckv.shape[1]

    # absorbed scores, storage dtype through the einsums (see mla_decode)
    q_abs = jnp.einsum("bhsk,rhk->bhsr", qn, params["w_k_b"]).astype(ckv.dtype)
    scores = (
        jnp.einsum("bhsr,btr->bhst", q_abs, ckv,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bhsk,btk->bhst", qr.astype(kr.dtype), kr,
                     preferred_element_type=jnp.float32)
    ) * ((spec.nope_head_dim + spec.rope_head_dim) ** -0.5)
    kpos = jnp.arange(Smax, dtype=jnp.int32)[None, None, :]
    valid = kpos < jnp.minimum(positions + 1, Smax)[:, :, None]  # (B,S,Smax)
    scores = jnp.where(valid[:, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(ckv.dtype)
    ctx = jnp.einsum("bhst,btr->bhsr", p, ckv,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    o = jnp.einsum("bhsr,rhk->bhsk", ctx, params["w_v_b"])
    out = jnp.einsum("bhsk,hkd->bsd", o, params["w_o"])
    return shard(out, "batch", "seq", "embed"), cache


def mla_decode(params, x, cache, lengths, spec: AttentionSpec, code: str = "F"):
    """Absorbed MLA decode: reads scale with kv_lora, not n_heads*d_head."""
    B = x.shape[0]
    pos4 = lengths[:, None, None]                   # for (B,H,1,dh)
    qn, qr = _mla_q(params, x, spec, pos4)          # (B,H,1,*)
    qn, qr = qn[:, :, 0], qr[:, :, 0]               # (B,H,nope/rope)

    ckv_new = jnp.einsum("bsd,dr->bsr", x, params["w_kv_a"])[:, 0]
    kr_new = rope(
        jnp.einsum("bsd,dk->bsk", x, params["w_k_rope"]),
        lengths[:, None], spec.rope_theta,
    )[:, 0]

    bidx = jnp.arange(B)
    Smax = cache["ckv"].shape[1]
    slot = jnp.minimum(lengths, Smax - 1)
    ckv = cache["ckv"].at[bidx, slot].set(ckv_new.astype(cache["ckv"].dtype))
    kr = cache["krope"].at[bidx, slot].set(kr_new.astype(cache["krope"].dtype))

    # absorb: q_eff[b,h,r] = sum_k qn[b,h,k] * w_k_b[r,h,k]
    # NOTE: the streamed buffer (ckv/kr, the per-token read of the whole
    # cache) stays in its STORAGE dtype through the einsums; upcasting the
    # operand would make XLA hoist an f32 convert of the entire stacked
    # cache out of the decode loop (observed: 3 GB/device buffers + 2x
    # cache HBM traffic).  f32 accumulation via preferred_element_type.
    q_abs = jnp.einsum("bhk,rhk->bhr", qn, params["w_k_b"]).astype(ckv.dtype)
    scores = (
        jnp.einsum("bhr,bsr->bhs", q_abs, ckv,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bhk,bsk->bhs", qr.astype(kr.dtype), kr,
                     preferred_element_type=jnp.float32)
    ) * ((spec.nope_head_dim + spec.rope_head_dim) ** -0.5)
    valid = jnp.arange(Smax)[None, :] < jnp.minimum(lengths + 1, Smax)[:, None]
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(ckv.dtype)
    ctx = jnp.einsum("bhs,bsr->bhr", p, ckv,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    o = jnp.einsum("bhr,rhk->bhk", ctx, params["w_v_b"])
    out = jnp.einsum("bhk,hkd->bd", o, params["w_o"])[:, None]
    return shard(out, "batch", "seq", "embed"), {"ckv": ckv, "krope": kr}


# ---------------------------------------------------------------------------
# Unified dispatch
# ---------------------------------------------------------------------------

def attn_train(params, x, spec, code):
    if spec.kind == "mla":
        return mla_train(params, x, spec, code)
    return gqa_train(params, x, spec, code)


def attn_prefill(params, x, cache, spec, code):
    if spec.kind == "mla":
        return mla_prefill(params, x, cache, spec, code)
    return gqa_prefill(params, x, cache, spec, code)


def attn_prefill_at(params, x, cache, offsets, new_lens, spec, code):
    if spec.kind == "mla":
        return mla_prefill_at(params, x, cache, offsets, new_lens, spec, code)
    return gqa_prefill_at(params, x, cache, offsets, new_lens, spec, code)
