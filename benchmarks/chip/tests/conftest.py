"""CPU tests of the chip benchmark: the harness imports as
``benchmarks.chip`` from the checkout's root, the program from ``src``."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _tiny_config(arch: str) -> dict:
    """A config file's contents at the program's smoke widths."""
    from repro.configs import smoke_config

    c = smoke_config(arch)
    a = c.attention
    return {
        "arch": arch,
        "family": "dense",
        "model": {
            "num_hidden_layers": c.n_layers, "hidden_size": c.d_model,
            "intermediate_size": c.d_ff, "num_attention_heads": a.n_heads,
            "num_key_value_heads": a.n_kv_heads, "head_dim": a.d_head,
            "vocab_size": c.vocab, "rope_theta": a.rope_theta,
            "hidden_act": "silu", "norm": c.norm, "norm_eps": 1e-6,
            # yi-6b's head is untied, as published
            "tie_word_embeddings": arch != "yi-6b", "dtype": "float32",
        },
        "serve": {"batch_slots": 4, "max_len": 64, "prefill_chunk": 8},
        "chips": 1,
        "correct": {"prefill_rel_rms": 0.015, "prefill_rel_max": 0.08,
                    "decode_rel_rms": 0.015, "decode_rel_max": 0.08,
                    "token_gap": 0.05, "sample_rows": 4},
    }


@pytest.fixture
def no_compile_cache(monkeypatch):
    """A run turns JAX's persistent cache on; keep this process's tests
    off it, as they found it."""
    import jax
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "off")
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was)


@pytest.fixture
def tiny_config():
    return _tiny_config


@pytest.fixture
def tiny_cell():
    """A cell at smoke size that serves a few requests in a second or
    two on the CPU."""
    from benchmarks.chip.cell import Cell, load_family

    def make(arch="olmo-1b", kind="offline"):
        mix = {
            "kind": kind, "rate_per_s": 6.0, "requests": 6,
            "prompt": {"dist": "uniform", "lo": 4, "hi": 20},
            "output": {"dist": "uniform", "lo": 3, "hi": 8},
        }
        config = _tiny_config(arch)
        return Cell(name="tiny", chips=1, config=config,
                    family=load_family(config["family"]), traffic=mix,
                    end_to_end=[], per_layer=[])

    return make
