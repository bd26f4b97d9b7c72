"""Decoder-only LM assembled from pattern stages (scan-over-layers).

A model is a sequence of *stages*; each stage scans one pattern period
(e.g. gemma3's ``LLLLLG``) with parameters stacked over repeats — one HLO
``while`` per stage, which keeps 512-device compiles fast and lets the
roofline analyzer multiply body costs by ``known_trip_count``.

Hybrid patterns: ``M`` layers are Mamba-2 blocks; ``S`` is the Zamba-style
*shared* attention block whose parameters live once at model level and are
closed over by every stage body (scan-invariant), with per-application KV
caches.

Decode carries each stage's stacked cache through its layer scan
(:func:`_run_stages_decode`): a GQA layer writes its new K/V row into the
stack in place and attention reads the same buffer, so a decode step
moves no layer-sized slice of the KV cache.  Prefill still scans the
cache as ``xs``/``ys`` (:func:`_run_stages_step`), slicing each layer out
and restacking it.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    apply_embed,
    apply_head,
    apply_mlp,
    apply_norm,
    cross_entropy,
    embed_defs,
    head_defs,
    mlp_defs,
    norm_defs,
)
from repro.models.sharding import (
    Param,
    current_rules,
    grad_cast,
    shard,
    shard_defs,
    stack_defs,
)

ATTN_CODES = ("F", "L", "G", "C")


# ---------------------------------------------------------------------------
# Param defs
# ---------------------------------------------------------------------------

def _layer_defs(cfg: ArchConfig, code: str, layer_idx: int) -> dict:
    d = cfg.d_model
    if code == "M":
        return {
            "norm": norm_defs(d, cfg.norm),
            "ssm": ssm_mod.ssm_defs(d, cfg.ssm),
        }
    if code == "S":
        return {}  # shared block params live at model level
    defs = {
        "attn_norm": norm_defs(d, cfg.norm),
        "attn": attn.attention_defs(d, cfg.attention),
        "mlp_norm": norm_defs(d, cfg.norm),
    }
    if cfg.moe is not None and cfg.moe.is_moe_layer(layer_idx):
        defs["moe"] = moe_mod.moe_defs(d, cfg.moe)
    else:
        ff = cfg.d_ff
        if cfg.moe is not None and cfg.moe.dense_d_ff:
            ff = cfg.moe.dense_d_ff
        defs["mlp"] = mlp_defs(d, ff)
    return defs


def _shared_block_defs(cfg: ArchConfig) -> dict:
    """Zamba shared attention over concat(hidden, emb0) -> d_model out."""
    dc = 2 * cfg.d_model
    a = cfg.attention
    defs = attn.attention_defs(dc, a)
    defs["w_o"] = Param(
        (a.n_heads, a.d_head, cfg.d_model), ("heads", "head_dim", "embed")
    )
    defs["norm"] = norm_defs(dc, cfg.norm)
    return defs


def _check_pattern(cfg: ArchConfig) -> None:
    if cfg.moe is not None and cfg.moe.moe_period > 1:
        assert len(cfg.layer_pattern) % cfg.moe.moe_period == 0, (
            "moe_period must divide the pattern length so scan bodies are "
            "homogeneous across repeats"
        )


def lm_defs(cfg: ArchConfig) -> dict:
    _check_pattern(cfg)
    defs: dict[str, Any] = {
        "embed": embed_defs(cfg.vocab, cfg.d_model),
        "final_norm": norm_defs(cfg.d_model, cfg.norm),
        "head": head_defs(cfg.vocab, cfg.d_model, cfg.tie_embeddings),
        "stages": [],
    }
    for codes, count, start in cfg.stages():
        stage = {
            f"{j}{code}": _layer_defs(cfg, code, start + j)
            for j, code in enumerate(codes)
        }
        defs["stages"].append(stack_defs(stage, count))
    if "S" in cfg.layer_pattern:
        defs["shared_attn"] = _shared_block_defs(cfg)
    return defs


# ---------------------------------------------------------------------------
# Cache defs
# ---------------------------------------------------------------------------

def lm_cache_defs(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    caches = {"stages": []}
    for codes, count, start in cfg.stages():
        stage = {}
        for j, code in enumerate(codes):
            if code == "M":
                stage[f"{j}{code}"] = ssm_mod.ssm_cache_defs(
                    batch, cfg.d_model, cfg.ssm
                )
            elif code == "S":
                stage[f"{j}{code}"] = attn.cache_defs(
                    batch, max_len, cfg.attention, "F"
                )
            else:
                stage[f"{j}{code}"] = attn.cache_defs(
                    batch, max_len, cfg.attention, code
                )
        caches["stages"].append(stack_defs(stage, count))
    return caches


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------

def _apply_layer_train(cfg, code, lp, x, emb0, shared):
    if code == "M":
        return x + ssm_mod.ssm_train(
            lp["ssm"], apply_norm(lp["norm"], x, cfg.norm), cfg.d_model, cfg.ssm
        ), 0.0
    if code == "S":
        xin = jnp.concatenate([x, emb0], axis=-1)
        xin = apply_norm(shared["norm"], xin, cfg.norm)
        return x + attn.gqa_train(shared, xin, cfg.attention, "F"), 0.0
    h = apply_norm(lp["attn_norm"], x, cfg.norm)
    x = x + attn.attn_train(lp["attn"], h, cfg.attention, code)
    h = apply_norm(lp["mlp_norm"], x, cfg.norm)
    if "moe" in lp:
        out, aux = moe_mod.apply_moe(lp["moe"], h, cfg.moe, cfg.act)
        return x + out, aux
    return x + apply_mlp(lp["mlp"], h, cfg.act), 0.0


def _apply_layer_step(
    cfg, code, lp, cache, x, emb0, lengths, shared, mode, new_lens=None
):
    """prefill/prefill_at step for one layer; returns (x, new_cache).

    ``prefill_at`` is the serving engine's chunked batched prefill:
    ``lengths`` carries each row's cache fill *offset* and ``new_lens`` how
    many of the chunk's positions are real for that row (0 = untouched).
    """
    if code == "M":
        h = apply_norm(lp["norm"], x, cfg.norm)
        if mode == "prefill":
            out, c = ssm_mod.ssm_prefill(lp["ssm"], h, cache, cfg.d_model, cfg.ssm)
        else:
            out, c = ssm_mod.ssm_prefill_at(
                lp["ssm"], h, cache, lengths, new_lens, cfg.d_model, cfg.ssm
            )
        return x + out, c
    if code == "S":
        xin = jnp.concatenate([x, emb0], axis=-1)
        xin = apply_norm(shared["norm"], xin, cfg.norm)
        if mode == "prefill":
            out, c = attn.gqa_prefill(shared, xin, cache, cfg.attention, "F")
        else:
            out, c = attn.gqa_prefill_at(
                shared, xin, cache, lengths, new_lens, cfg.attention, "F"
            )
        return x + out, c
    h = apply_norm(lp["attn_norm"], x, cfg.norm)
    if mode == "prefill":
        out, c = attn.attn_prefill(lp["attn"], h, cache, cfg.attention, code)
    else:
        out, c = attn.attn_prefill_at(
            lp["attn"], h, cache, lengths, new_lens, cfg.attention, code
        )
    return _apply_ffn(cfg, lp, x + out), c


def _apply_ffn(cfg, lp, x):
    """The MLP (or MoE) half of an attention layer, residual included."""
    h = apply_norm(lp["mlp_norm"], x, cfg.norm)
    if "moe" in lp:
        out, _ = moe_mod.apply_moe(lp["moe"], h, cfg.moe, cfg.act)
        return x + out
    return x + apply_mlp(lp["mlp"], h, cfg.act)


def _at_layer(fn, cache, i):
    """``fn`` on layer ``i`` of a stacked cache; returns ``fn``'s output
    and the cache with the new layer written back at ``i``."""
    out, c = fn(jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), cache
    ))
    return out, jax.tree.map(
        lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, i, 0),
        cache, c,
    )


def _apply_layer_decode(cfg, code, lp, cache, i, x, emb0, lengths, shared):
    """Decode one layer against layer ``i`` of its stacked cache; returns
    (x, cache).  GQA caches (full, ring ``L``, chunk ``C``, the shared
    ``S`` block's) take the new row in place; SSM state and MLA latents
    are sliced out and written back."""
    if code == "M":
        h = apply_norm(lp["norm"], x, cfg.norm)
        out, cache = _at_layer(
            lambda c: ssm_mod.ssm_decode(lp["ssm"], h, c, cfg.d_model, cfg.ssm),
            cache, i,
        )
        return x + out, cache
    if code == "S":
        xin = jnp.concatenate([x, emb0], axis=-1)
        xin = apply_norm(shared["norm"], xin, cfg.norm)
        out, cache = attn.gqa_decode_at(
            shared, xin, cache, i, lengths, cfg.attention, "F"
        )
        return x + out, cache
    h = apply_norm(lp["attn_norm"], x, cfg.norm)
    if cfg.attention.kind == "mla":
        out, cache = _at_layer(
            lambda c: attn.mla_decode(
                lp["attn"], h, c, lengths, cfg.attention, code
            ),
            cache, i,
        )
    else:
        out, cache = attn.gqa_decode_at(
            lp["attn"], h, cache, i, lengths, cfg.attention, code
        )
    return _apply_ffn(cfg, lp, x + out), cache


# ---------------------------------------------------------------------------
# Stage scans
# ---------------------------------------------------------------------------

def _run_stages_train(cfg, params, x, remat: str):
    shared = params.get("shared_attn")
    emb0 = x if "S" in cfg.layer_pattern else jnp.zeros((1,), x.dtype)
    aux_total = 0.0
    fsdp = tuple(current_rules().get("fsdp", ()))
    for (codes, count, start), stage_params in zip(
        cfg.stages(), params["stages"]
    ):
        stage_defs = {
            f"{j}{code}": _layer_defs(cfg, code, start + j)
            for j, code in enumerate(codes)
        }

        grad_dtype = current_rules().get("grad_dtype")

        def body(carry, lp, _codes=codes, _defs=stage_defs):
            x, emb0, aux = carry
            # pin the layer-slice params (and, via AD transpose, their
            # grads) to the per-layer FSDP sharding inside the loop.
            lp = shard_defs(lp, _defs, fsdp)
            for j, code in enumerate(_codes):
                x, a = _apply_layer_train(
                    cfg, code, lp[f"{j}{code}"], x, emb0, shared
                )
                aux = aux + a
            x = shard(x, "batch", "seq", "embed")
            if grad_dtype:
                x = grad_cast(x, grad_dtype)
            return (x, emb0, aux), None

        if remat == "full":
            body = jax.checkpoint(body)
        elif remat == "dots":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            )
        (x, emb0, aux_total), _ = jax.lax.scan(
            body, (x, emb0, aux_total), stage_params
        )
    return x, aux_total


def _run_stages_step(cfg, params, caches, x, lengths, mode, new_lens=None):
    """Prefill scan: each layer's cache is sliced in as ``xs`` and
    restacked as ``ys``.  Its cache write is a chunk at unaligned offsets;
    prefill is expected to join :func:`_run_stages_decode` once a chunk
    writer exists."""
    shared = params.get("shared_attn")
    emb0 = x if "S" in cfg.layer_pattern else jnp.zeros((1,), x.dtype)
    new_caches = []
    for (codes, count, start), stage_params, stage_cache in zip(
        cfg.stages(), params["stages"], caches["stages"]
    ):
        def body(carry, slices, _codes=codes):
            x, emb0 = carry
            lp, cache = slices
            new_cache = {}
            for j, code in enumerate(_codes):
                key = f"{j}{code}"
                x, c = _apply_layer_step(
                    cfg, code, lp[key], cache[key], x, emb0, lengths,
                    shared, mode, new_lens,
                )
                new_cache[key] = c
            x = shard(x, "batch", "seq", "embed")
            return (x, emb0), new_cache

        (x, emb0), nc = jax.lax.scan(
            body, (x, emb0), (stage_params, stage_cache)
        )
        new_caches.append(nc)
    return x, {"stages": new_caches}


def _run_stages_decode(cfg, params, caches, x, lengths):
    """Decode scan: each stage's stacked cache and the layer index ride
    in the carry; parameters stay ``xs``.

    A GQA layer writes its new K/V row into the stack in place and its
    attention reads the same buffer at the layer index, so no layer slice
    of the cache is materialised (the ``xs``/``ys`` scan of
    :func:`_run_stages_step` copies every layer out and back, and the
    whole stack once more).  Prefill is expected to join this scan once a
    writer for its chunks exists.
    """
    shared = params.get("shared_attn")
    emb0 = x if "S" in cfg.layer_pattern else jnp.zeros((1,), x.dtype)
    new_caches = []
    for (codes, _, _), stage_params, stage_cache in zip(
        cfg.stages(), params["stages"], caches["stages"]
    ):
        def body(carry, lp, _codes=codes):
            x, cache, i = carry
            cache = dict(cache)
            for j, code in enumerate(_codes):
                key = f"{j}{code}"
                x, cache[key] = _apply_layer_decode(
                    cfg, code, lp[key], cache[key], i, x, emb0, lengths,
                    shared,
                )
            x = shard(x, "batch", "seq", "embed")
            return (x, cache, i + 1), None

        (x, cache, _), _ = jax.lax.scan(
            body, (x, stage_cache, jnp.int32(0)), stage_params
        )
        new_caches.append(cache)
    return x, {"stages": new_caches}


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def lm_forward(
    params,
    tokens: jax.Array,              # (B, S_text)
    cfg: ArchConfig,
    *,
    extra_embeds: jax.Array | None = None,   # (B, S_front, d) modality stub
    remat: str = "none",
):
    """Training-mode forward -> (logits, aux_loss)."""
    x = apply_embed(params["embed"], tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        x = shard(x, "batch", "seq", "embed")
    x, aux = _run_stages_train(cfg, params, x, remat)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = apply_head(params["head"], params["embed"], x)
    return logits, aux


def lm_loss(
    params, tokens, labels, cfg: ArchConfig, *,
    extra_embeds=None, remat: str = "full", aux_weight: float = 0.01,
):
    from repro.models.layers import fused_cross_entropy

    # forward up to the final hidden states, then head+CE fused per
    # sequence block: the full (B,S,V) f32 logits chain never exists.
    x = apply_embed(params["embed"], tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        x = shard(x, "batch", "seq", "embed")
    x, aux = _run_stages_train(cfg, params, x, remat)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    if extra_embeds is not None:
        x = x[:, extra_embeds.shape[1]:]
    loss = fused_cross_entropy(params["head"], params["embed"], x, labels)
    return loss + aux_weight * aux, {"ce": loss, "aux": aux}


def lm_prefill(params, tokens, caches, cfg: ArchConfig, *, extra_embeds=None):
    """Fill the cache from a prompt; returns (last-token logits, caches)."""
    x = apply_embed(params["embed"], tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    lengths = jnp.full((tokens.shape[0],), x.shape[1], jnp.int32)
    x, caches = _run_stages_step(cfg, params, caches, x, lengths, "prefill")
    x = apply_norm(params["final_norm"], x[:, -1:], cfg.norm)
    logits = apply_head(params["head"], params["embed"], x)
    return logits[:, 0], caches


def lm_prefill_at(params, tokens, caches, offsets, new_lens, cfg: ArchConfig):
    """Chunked batched prefill: write one prompt chunk per row at an offset.

    ``tokens`` (B, S) holds one chunk of each row's prompt; row ``b``
    appends ``new_lens[b] <= S`` tokens at cache positions ``offsets[b]..``
    (``new_lens == 0`` leaves the row's cache untouched — rows mid-decode
    ride through the dispatch unharmed).  Returns the logits of each row's
    last *valid* chunk position (garbage for ``new_lens == 0`` rows) and
    the updated caches.  This is the serving engine's replacement for
    replaying prompts token-by-token through full-batch decode steps:
    admitting a batch of length-L prompts costs O(L / chunk) dispatches
    instead of O(B·L), and the prior cache is read once per chunk.
    """
    x = apply_embed(params["embed"], tokens)
    x, caches = _run_stages_step(
        cfg, params, caches, x, offsets, "prefill_at", new_lens
    )
    last = jnp.clip(new_lens - 1, 0, tokens.shape[1] - 1)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)   # (B,1,d)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = apply_head(params["head"], params["embed"], x)
    return logits[:, 0], caches


def lm_decode_step(params, tokens, caches, lengths, cfg: ArchConfig):
    """One decode step; tokens (B,1); lengths (B,) current cache fill.

    Returns (logits (B, vocab), new caches).  Caller advances lengths.
    """
    x = apply_embed(params["embed"], tokens)
    x, caches = _run_stages_decode(cfg, params, caches, x, lengths)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = apply_head(params["head"], params["embed"], x)
    return logits[:, 0], caches
