"""The float32 reference forward against the model's own paths (CPU).

``repro.models.reference`` is what the served logits are held to on the
chip; here it is checked at smoke widths against the training forward and
against the serving path (chunked ``prefill_at`` + cached ``decode_step``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.model_zoo import get_smoke_bundle
from repro.models.reference import forward_logits
from repro.models.transformer import lm_forward

ARCHS = ["olmo-1b", "gemma3-27b", "yi-6b"]


def _setup(arch, seq=24, batch=2):
    bundle = get_smoke_bundle(arch)
    params = bundle.init_params(jax.random.PRNGKey(0), "float32")
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq), 0, bundle.cfg.vocab
    )
    return bundle, params, tokens


@pytest.mark.parametrize("arch", ARCHS)
def test_reference_matches_training_forward(arch):
    bundle, params, tokens = _setup(arch)
    want = forward_logits(params, tokens, bundle.cfg)
    with jax.default_matmul_precision("highest"):
        got, _ = lm_forward(params, tokens, bundle.cfg)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("arch", ARCHS)
def test_reference_matches_chunked_prefill_and_decode(arch):
    """Chunk-prefill the first 20 tokens 8 at a time, then decode 4: each
    chunk's last-position logits and each decode step's logits match the
    reference at the same position."""
    bundle, params, tokens = _setup(arch)
    want = np.asarray(forward_logits(params, tokens, bundle.cfg))
    B, S = tokens.shape
    caches = bundle.init_cache(B, 32, "float32")
    prefill = jax.jit(bundle.prefill_at)
    decode = jax.jit(bundle.decode_step)
    with jax.default_matmul_precision("highest"):
        for lo in range(0, 20, 8):
            n = min(8, 20 - lo)
            chunk = jnp.zeros((B, 8), jnp.int32).at[:, :n].set(
                tokens[:, lo:lo + n]
            )
            logits, caches = prefill(
                params, {"tokens": chunk, "new_lens": jnp.full((B,), n)},
                caches, jnp.full((B,), lo, jnp.int32),
            )
            np.testing.assert_allclose(
                logits, want[:, lo + n - 1], atol=2e-4, rtol=2e-4
            )
        for pos in range(20, S):
            logits, caches = decode(
                params,
                {"tokens": tokens[:, pos:pos + 1],
                 "lengths": jnp.full((B,), pos, jnp.int32)},
                caches,
            )
            np.testing.assert_allclose(
                logits, want[:, pos], atol=2e-4, rtol=2e-4
            )


def test_degraded_attention_moves_the_logits():
    """The fp8-attention variant differs from the reference by far more
    than the float32 paths differ from each other."""
    bundle, params, tokens = _setup("olmo-1b")
    want = forward_logits(params, tokens, bundle.cfg)
    low = forward_logits(
        params, tokens, bundle.cfg, attn_dtype=jnp.float8_e4m3fn
    )
    assert float(jnp.max(jnp.abs(low - want))) > 1e-2


@pytest.mark.parametrize("arch", ["mamba2-780m", "deepseek-v2-236b"])
def test_reference_refuses_other_families(arch):
    bundle = get_smoke_bundle(arch)
    with pytest.raises(NotImplementedError):
        forward_logits({}, jnp.zeros((1, 4), jnp.int32), bundle.cfg)
