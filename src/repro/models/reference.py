"""Plain float32 reference forward for dense decoder-only configs.

Written independently of the served path: no KV cache, no chunking, no
scan, no kernel dispatch — one loop over layers with attention from
:mod:`repro.kernels.ref`, every matmul at ``highest`` precision.  The
served logits (chunked prefill + cached decode, Pallas kernels on TPU,
bfloat16 weights and activations) are compared against it.

Covers the dense grouped-query family (``F``/``G``/``L``/``C`` layers,
any norm kind, gated MLP, tied or untied head, optional q/k norm); other
families raise ``NotImplementedError``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.kernels import ref

_MASK_KIND = {"F": "causal", "G": "causal", "L": "sliding", "C": "chunked"}


def _norm(x, p, kind, eps=1e-6):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(jnp.var(x, -1, keepdims=True) + eps)
    if kind == "layernorm":
        y = y * p["scale"] + p["bias"]
    return y


def _rope(x, theta):
    """Rotate-half RoPE over (B, H, S, D) at positions 0..S-1."""
    d = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(d, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d], x[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _check_supported(cfg: ArchConfig) -> None:
    a = cfg.attention
    if (
        cfg.family != "dense" or cfg.moe is not None or cfg.ssm is not None
        or a is None or a.kind != "gqa" or cfg.frontend != "none"
        or set(cfg.layer_pattern) - set(_MASK_KIND)
    ):
        raise NotImplementedError(
            f"reference forward covers dense GQA decoders; {cfg.name} "
            f"is {cfg.family} with pattern {cfg.layer_pattern!r}"
        )


def forward_logits(params, tokens, cfg: ArchConfig, *, attn_dtype=None):
    """(B, S) tokens -> (B, S, vocab) float32 logits.

    ``params`` is the model's own pytree (any dtype; upcast here).
    ``attn_dtype`` rounds q, k and v to that dtype before attention — a
    deliberately degraded variant that shows a tolerance can tell a
    lower-precision attention apart from the reference.
    """
    _check_supported(cfg)
    a = cfg.attention
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
           "relu": jax.nn.relu}[cfg.act]
    p32 = jax.tree.map(lambda w: w.astype(jnp.float32), params)

    def mm(spec, x, w):
        return jnp.einsum(spec, x, w, precision="highest")

    with jax.default_matmul_precision("highest"):
        x = p32["embed"]["embedding"][tokens]
        for (codes, count, _), stage in zip(cfg.stages(), p32["stages"]):
            for r in range(count):
                for j, code in enumerate(codes):
                    lp = jax.tree.map(lambda w: w[r], stage[f"{j}{code}"])
                    at = lp["attn"]
                    h = _norm(x, lp["attn_norm"], cfg.norm)
                    q = mm("bsd,dhk->bhsk", h, at["w_q"])
                    k = mm("bsd,dhk->bhsk", h, at["w_k"])
                    v = mm("bsd,dhk->bhsk", h, at["w_v"])
                    if a.qk_norm:
                        q = _norm(q, {"scale": at["q_norm"]}, "rmsnorm")
                        k = _norm(k, {"scale": at["k_norm"]}, "rmsnorm")
                    theta = (a.rope_theta_global
                             if code == "G" and a.rope_theta_global
                             else a.rope_theta)
                    q, k = _rope(q, theta), _rope(k, theta)
                    if attn_dtype is not None:
                        q, k, v = (
                            t.astype(attn_dtype).astype(jnp.float32)
                            for t in (q, k, v)
                        )
                    o = ref.attention(
                        q, k, v, kind=_MASK_KIND[code],
                        window=a.window, chunk=a.chunk,
                    )
                    x = x + mm("bhsk,hkd->bsd", o, at["w_o"])
                    h = _norm(x, lp["mlp_norm"], cfg.norm)
                    m = lp["mlp"]
                    g = act(mm("bsd,df->bsf", h, m["w_gate"]))
                    u = mm("bsd,df->bsf", h, m["w_up"])
                    x = x + mm("bsf,fd->bsd", g * u, m["w_down"])
        x = _norm(x, p32["final_norm"], cfg.norm)
        head = p32["head"]
        w = (head["unembed"] if "unembed" in head
             else p32["embed"]["embedding"].T)
        return mm("bsd,dv->bsv", x, w)
