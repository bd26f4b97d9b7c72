"""Operations and bytes the served work needs, from shapes and live
lengths alone.

Every count here is of the work the algorithm needs, never of what an
implementation happens to read or compute: padded prefill rows, dead
cache tiles and idle slots count for nothing.  So a kernel that learns
to skip them raises its share of the roofline without reading past 100%.
FLOPs count a multiply-add as two.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Model:
    """Sizes of a dense grouped-query decoder, as served."""

    n_layers: int
    d_model: int
    d_ff: int
    vocab: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    norm: str                 # "rmsnorm" | "nonparametric"
    tied: bool
    bytes_per_value: int = 2  # bfloat16 weights, activations and cache

    @classmethod
    def from_config(cls, model: dict) -> "Model":
        return cls(
            n_layers=model["num_hidden_layers"],
            d_model=model["hidden_size"],
            d_ff=model["intermediate_size"],
            vocab=model["vocab_size"],
            n_heads=model["num_attention_heads"],
            n_kv_heads=model["num_key_value_heads"],
            d_head=model["head_dim"],
            norm=model["norm"],
            tied=model["tie_word_embeddings"],
            bytes_per_value={"bfloat16": 2, "float32": 4}[model["dtype"]],
        )

    # -- weights -------------------------------------------------------
    def layer_matmul_params(self) -> int:
        """q, k, v, o projections and the gated MLP of one layer."""
        d, hd = self.d_model, self.d_head
        attn = d * self.n_heads * hd * 2 + d * self.n_kv_heads * hd * 2
        return attn + 3 * d * self.d_ff

    def layer_norm_params(self) -> int:
        return 2 * self.d_model if self.norm == "rmsnorm" else 0

    def decode_weight_bytes(self) -> int:
        """Every weight a decode step reads once: the layers, the norms
        and the output head (the embedding table's rows are counted
        with the live rows)."""
        final = self.d_model if self.norm == "rmsnorm" else 0
        n = (self.n_layers * (self.layer_matmul_params()
                              + self.layer_norm_params())
             + final + self.vocab * self.d_model)
        return n * self.bytes_per_value

    def kv_bytes_per_position(self) -> int:
        """K and V of one position, over all layers."""
        return (2 * self.n_layers * self.n_kv_heads * self.d_head
                * self.bytes_per_value)

    # -- FLOPs ---------------------------------------------------------
    def linear_flops_per_token(self) -> int:
        return 2 * self.n_layers * self.layer_matmul_params()

    def head_flops_per_token(self) -> int:
        return 2 * self.d_model * self.vocab

    def attn_flops(self, first: int, n: int) -> int:
        """Causal attention FLOPs (QK^T and PV) of ``n`` queries at
        positions ``first .. first+n-1``, all layers: the query at
        position t attends to t + 1 keys."""
        keys = n * first + n * (n + 1) // 2
        return 4 * self.n_layers * self.n_heads * self.d_head * keys

    def prefill_flops(self, n: int) -> int:
        """Writing an ``n``-token prompt into the cache: the layers at
        every position, no output head (the first token's logits come
        from the decode step that feeds the prompt's last token)."""
        return n * self.linear_flops_per_token() + self.attn_flops(0, n)

    def decode_flops(self, position: int) -> int:
        """One decoded token fed at ``position``."""
        return (self.linear_flops_per_token() + self.head_flops_per_token()
                + self.attn_flops(position, 1))

    # -- one decode step -----------------------------------------------
    def decode_step_bytes(self, fills) -> int:
        """Bytes a decode step needs with live rows at cache fills
        ``fills``: every weight once, each live row's embedding, K/V of
        its live positions (``fill + 1`` with the new one) read, and the
        new K/V row written."""
        kv = self.kv_bytes_per_position()
        rows = len(fills)
        return (self.decode_weight_bytes()
                + rows * self.d_model * self.bytes_per_value
                + sum(f + 1 for f in fills) * kv + rows * kv)

    # -- kernels -------------------------------------------------------
    def flash_decode_bytes(self, fills) -> int:
        """The decode attention kernel over all layers: per live row its
        query, K/V of its ``fill + 1`` positions, and its output."""
        b = self.bytes_per_value
        q_out = 2 * self.n_heads * self.d_head * b
        kv = 2 * self.n_kv_heads * self.d_head * b
        per_layer = sum(q_out + (f + 1) * kv for f in fills)
        return self.n_layers * per_layer

    def flash_prefill_work(self, chunks) -> tuple[int, int]:
        """(FLOPs, bytes) of the chunked-prefill attention kernel over
        all layers, for admitted rows' chunks ``[(offset, n), ...]``:
        causal FLOPs of the real queries, and their q, the K/V of the
        ``offset + n`` positions they see, and their output."""
        b = self.bytes_per_value
        flops = sum(self.attn_flops(o, n) for o, n in chunks)
        q_out = 2 * self.n_heads * self.d_head * b
        kv = 2 * self.n_kv_heads * self.d_head * b
        per_layer = sum(n * q_out + (o + n) * kv for o, n in chunks)
        return flops, self.n_layers * per_layer


def prompt_chunks(n: int, chunk: int) -> list[tuple[int, int]]:
    """(offset, length) of the chunks that write an ``n``-token prompt."""
    return [(lo, min(chunk, n - lo)) for lo in range(0, n, chunk)]
