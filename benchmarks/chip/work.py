"""Operations and bytes a decoder-only transformer step needs, from the
configuration's shapes alone (never from the implementation).

Counting rules:
  * matmul FLOPs: 2 x the matmul parameters per token, LM head included
    (the embedding gather is not a matmul);
  * attention FLOPs: 4 x layers x query heads x head_dim x live context
    per token (scores and the weighted sum of values);
  * bytes per decode step: every weight once, plus the live KV cache of
    each active slot (2 x layers x KV heads x head_dim x 2 B per token).
"""
from __future__ import annotations

from typing import Iterable, NamedTuple


class Shapes(NamedTuple):
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    dtype_bytes: int = 2


def shapes_of(config: dict) -> Shapes:
    """The shapes of a configuration file (Hugging Face key names)."""
    return Shapes(
        layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        dtype_bytes={"bfloat16": 2, "float16": 2, "float32": 4}[
            config["torch_dtype"]])


def matmul_params(s: Shapes) -> int:
    """Parameters that take part in a matmul per token (LM head included)."""
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    per_layer = s.d_model * q + 2 * s.d_model * kv + q * s.d_model \
        + 3 * s.d_model * s.d_ff
    return s.layers * per_layer + s.vocab * s.d_model


def weight_bytes(s: Shapes) -> int:
    """Bytes of every weight read once (tied embedding counted once)."""
    norms = s.layers * (2 * s.d_model + 2 * s.head_dim) + s.d_model
    return (matmul_params(s) + norms) * s.dtype_bytes


def kv_bytes_per_token(s: Shapes) -> int:
    return 2 * s.layers * s.kv_heads * s.head_dim * s.dtype_bytes


def token_flops(s: Shapes, context: int) -> float:
    """FLOPs to run one token whose attention sees ``context`` positions."""
    return 2.0 * matmul_params(s) + 4.0 * s.layers * s.heads * s.head_dim * context


def tokens_flops(s: Shapes, contexts: Iterable[int]) -> float:
    contexts = list(contexts)
    return (2.0 * matmul_params(s) * len(contexts)
            + 4.0 * s.layers * s.heads * s.head_dim * sum(contexts))


def decode_steps_bytes(s: Shapes, steps: int, contexts: Iterable[int]) -> float:
    """Bytes of ``steps`` decode steps whose active slots, summed over the
    steps, read the KV of ``contexts`` positions each."""
    return float(steps) * weight_bytes(s) + float(sum(contexts)) * kv_bytes_per_token(s)


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bytes_s: float) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bytes_s)
