"""Plain float32 forward of a dense grouped-query decoder, layer by layer,
and the statistics of any family's reference logits that the check reads.

Written from the layer equations, independent of the program: token
embedding; per layer a pre-norm (OLMo's non-parametric LayerNorm or
RMSNorm with a scale), q/k/v projections, rotate-half RoPE, causal
softmax attention with grouped KV heads, the output projection and a
SiLU-gated MLP, each added to the residual; a final norm and the output
head (the embedding, transposed, when tied).  Every matmul runs at the
highest precision in float32.

It reads the weights the harness made (served dtype, upcast one layer at
a time) in the parameter tree's layout, and one sequence at a time at a
fixed padded length, so one compiled layer serves every call and the
float32 copy never holds more than a layer.

``low=`` is the control: the same forward computed in the precision
below the served one (float8 for a bfloat16 model).  With
``scope="qkv"`` q, k and v are rounded to it before attention, as a
float8 KV cache would hold them; with ``scope="all"`` both operands of
every matmul are rounded too, as float8 weights and activations would be.

``mm``, ``norm``, ``rope`` and ``head_weight`` are the parts another
family's reference (``families/<family>.py``) builds its layers from;
``position_stats`` takes any family's ``forward``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def mm(spec, a, b, low=None):
    a, b = a.astype(F32), b.astype(F32)
    if low is not None:
        a, b = (t.astype(low).astype(F32) for t in (a, b))
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def norm(x, p, kind, eps):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * p["scale"].astype(F32)
    if kind == "nonparametric":
        mu = jnp.mean(x, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(jnp.var(x, -1, keepdims=True) + eps)
    raise ValueError(f"unknown norm {kind!r}")


def rope(x, theta):
    """Rotate-half RoPE over (H, T, D) at positions 0..T-1."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(model, low, scope, stage, r, x):
    """One layer at index ``r`` of a stacked stage; x (T, d) float32."""
    lp = jax.tree.map(lambda w: w[r], stage)
    qkv_low, low = low, (low if scope == "all" else None)
    kind, eps = model["norm"], model["norm_eps"]
    at = lp["attn"]
    h = norm(x, lp["attn_norm"], kind, eps)
    q = mm("td,dhk->htk", h, at["w_q"], low)
    k = mm("td,dhk->htk", h, at["w_k"], low)
    v = mm("td,dhk->htk", h, at["w_v"], low)
    q, k = rope(q, model["rope_theta"]), rope(k, model["rope_theta"])
    if qkv_low is not None:
        q, k, v = (t.astype(qkv_low).astype(F32) for t in (q, k, v))
    hkv, T, dh = k.shape
    qg = q.reshape(hkv, q.shape[0] // hkv, T, dh)
    s = jnp.einsum("hgqd,hkd->hgqk", qg, k, precision=HIGHEST) / np.sqrt(dh)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgqk,hkd->hgqd", p, v, precision=HIGHEST)
    x = x + mm("htk,hkd->td", o.reshape(q.shape), at["w_o"], low)
    h = norm(x, lp["mlp_norm"], kind, eps)
    m = lp["mlp"]
    g = jax.nn.silu(mm("td,df->tf", h, m["w_gate"], low))
    u = mm("td,df->tf", h, m["w_up"], low)
    return x + mm("tf,fd->td", g * u, m["w_down"], low)


def head_weight(params):
    head = params["head"]
    if "unembed" in head:
        return head["unembed"]
    return params["embed"]["embedding"].T


def _logits(model, low, final_norm, w, x):
    kind, eps = model["norm"], model["norm_eps"]
    return mm("td,dv->tv", norm(x, final_norm, kind, eps), w, low)


def _stats(logits, targets):
    """Per position: the best logit, the logit of the target token, the
    sum of squared logits, and the first token."""
    at = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return (logits.max(-1), at, jnp.sum(logits * logits, -1),
            jnp.argmax(logits, -1))


def _tapped(ref, pos, other):
    """At positions ``pos``: the sum of squared reference logits, and the
    sum of squares and the largest magnitude of ``other - ref``."""
    r = ref[pos]
    d = other - r
    return jnp.sum(r * r, -1), jnp.sum(d * d, -1), jnp.abs(d).max(-1)


def _at_first(ref, other):
    """The reference's logit of the token that ``other`` puts first."""
    return jnp.take_along_axis(ref, jnp.argmax(other, -1)[:, None], -1)[:, 0]


@functools.lru_cache(maxsize=None)
def _compiled(model_items, low_name, scope):
    model = dict(model_items)
    low = None if low_name is None else jnp.dtype(low_name)
    layer = jax.jit(functools.partial(_layer, model, low, scope))
    head_low = low if scope == "all" else None
    logits = jax.jit(functools.partial(_logits, model, head_low))
    return layer, logits


_embed = jax.jit(lambda e, t: e[t].astype(F32))
_stats_jit = jax.jit(_stats)
_tapped_jit = jax.jit(_tapped)
_at_first_jit = jax.jit(_at_first)
#: tapped positions are padded to a multiple of this, to bound compiles
BUCKET = 128


def forward(params, model: dict, toks: np.ndarray, low=None,
            scope: str = "all") -> jax.Array:
    """(len(toks), vocab) float32 logits on the device, one layer at a
    time; ``low``/``scope`` as in the module's docstring."""
    layer, logits = _compiled(tuple(sorted(model.items())),
                              None if low is None else jnp.dtype(low).name,
                              scope)
    x = _embed(params["embed"]["embedding"], jnp.asarray(toks))
    for stage in params["stages"]:
        for sub in stage.values():
            for r in range(jax.tree.leaves(sub)[0].shape[0]):
                x = layer(sub, jnp.int32(r), x)
    return logits(params["final_norm"], head_weight(params), x)


def position_stats(params, forward, model: dict, seq: np.ndarray,
                   length: int, pos=None, served=None, low=None,
                   scope: str = "all"):
    """Reference statistics of ``seq`` (int tokens), padded to
    ``length``: position t holds the logits that predict ``seq[t + 1]``
    in the family's reference ``forward`` (``families/<family>.py``).

    Returns a dict of host arrays over the ``len(seq) - 1`` positions:
    ``best``, ``at`` (the logit of the next token), ``sumsq``, ``first``.
    With ``pos`` (positions) and ``served`` (the logits served there,
    one row each): ``ref_sumsq``, ``err_sumsq`` and ``err_max`` at
    ``pos``.  With ``low``, the control: ``at_low`` (the reference's
    logit of the token the control puts first) and, with ``pos``,
    ``low_err_sumsq`` and ``low_err_max`` (the control's logits against
    the reference's there)."""
    n = len(seq)
    if n > length:
        raise ValueError(f"sequence of {n} tokens exceeds {length}")
    toks = np.zeros(length, np.int32)
    toks[:n] = seq
    targets = np.zeros(length, np.int32)
    targets[: n - 1] = seq[1:]
    ref = forward(params, model, toks)
    names = ("best", "at", "sumsq", "first")
    out = {k: np.asarray(a)[: n - 1]
           for k, a in zip(names, _stats_jit(ref, jnp.asarray(targets)))}
    if pos is not None:
        k = len(pos)
        padded = -(-max(k, 1) // BUCKET) * BUCKET
        p = np.zeros(padded, np.int32)
        p[:k] = pos
        p = jnp.asarray(p)
    if served is not None:
        rows = np.zeros((padded, ref.shape[1]), np.float32)
        rows[:k] = served
        for name, a in zip(("ref_sumsq", "err_sumsq", "err_max"),
                           _tapped_jit(ref, p, jnp.asarray(rows))):
            out[name] = np.asarray(a)[:k]
    if low is not None:
        other = forward(params, model, toks, low, scope)
        out["at_low"] = np.asarray(_at_first_jit(ref, other))[: n - 1]
        if pos is not None:
            _, e2, em = _tapped_jit(ref, p, other[p])
            out["low_err_sumsq"] = np.asarray(e2)[:k]
            out["low_err_max"] = np.asarray(em)[:k]
    return out
