"""Dense full-attention decoders with grouped-query heads (olmo-1b, yi-6b).

A family module gives the harness what depends on the model's kind,
found by the ``family`` key of a config file (``families/<family>.py``):

- ``arch_config(config)``: the program's ``ArchConfig`` with the sizes
  the file states; any size the program would run differently is an
  error;
- ``counts(model)``: the needed work of the file's ``model``, with the
  methods the readers call (``prefill_flops``, ``decode_flops``,
  ``decode_step_bytes``, ``attn_flops``, ``flash_decode_bytes``,
  ``flash_prefill_work``, and the sizes a kernel's result shape is
  built from);
- ``forward(params, model, toks, low=None, scope="all")``: the float32
  reference's logits, with the control's lower precision (``scope``
  ``"qkv"`` rounds what the family's cache would hold, ``"all"`` also
  every matmul operand);
- ``fan_in(name, shape, stacked)``: the fan-in that scales a weight's
  initialisation.
"""

from __future__ import annotations

import dataclasses

from benchmarks.chip import counts as _counts
from benchmarks.chip import reference


def arch_config(config: dict):
    from repro.configs import get_config

    m = config["model"]
    base = get_config(config["arch"])
    cfg = dataclasses.replace(
        base,
        n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"],
        d_ff=m["intermediate_size"],
        vocab=m["vocab_size"],
        norm=m["norm"],
        tie_embeddings=m["tie_word_embeddings"],
        dtype=m["dtype"],
        act={"silu": "silu"}[m["hidden_act"]],
        attention=dataclasses.replace(
            base.attention,
            n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"],
            d_head=m["head_dim"],
            rope_theta=float(m["rope_theta"]),
        ),
    )
    if cfg.layer_pattern != "F" or cfg.family != "dense":
        raise NotImplementedError(
            f"{cfg.name}: the dense family serves full-attention decoders"
        )
    return cfg


def counts(model: dict) -> _counts.Model:
    return _counts.Model.from_config(model)


def forward(params, model: dict, toks, low=None, scope: str = "all"):
    if {k[1:] for s in params["stages"] for k in s} != {"F"}:
        raise NotImplementedError("reference covers full-attention layers")
    return reference.forward(params, model, toks, low, scope)


def fan_in(name: str, shape, stacked: bool) -> int:
    dims = shape[1:] if stacked else shape
    if name == "w_o":            # (heads, head_dim, d_model)
        return dims[0] * dims[1]
    return dims[0]
