"""Operations and bytes a model step needs, from the configuration's shapes
alone (never from the implementation).

Each architecture's module (``archs/<model_type>.py``) gives a work
model, ``shapes(config)``, with:

  ``layers``, ``vocab``
  ``matmul_params``       parameters that take part in a matmul for one
                          token (LM head included; for sparse experts only
                          the experts a token is routed to)
  ``weight_bytes``        bytes of every weight, read once
  ``kv_bytes_per_token``  cache bytes one position holds over all layers
  ``tokens_flops(contexts)``
                          FLOPs to run one token per entry of ``contexts``,
                          whose attention sees that many positions
  ``decode_steps_bytes(steps, contexts)``
                          bytes of ``steps`` decode steps whose active
                          slots, summed over the steps, read the cache of
                          ``contexts`` positions each (for sparse experts,
                          the weights a step's tokens can touch)

Each module writes its own counting rule, from the shared rules below:
  * matmul FLOPs: 2 x the matmul parameters per token (the embedding
    gather is not a matmul);
  * attention FLOPs: 4 x (layers x query heads x head_dim summed over the
    layers that attend) x live context per token (scores and the weighted
    sum of values);
  * bytes per decode step: the weights the step reads, plus the live cache
    of each active slot.
"""
from __future__ import annotations

from typing import Dict, Iterable

DTYPE_BYTES: Dict[str, int] = {"bfloat16": 2, "float16": 2, "float32": 4}


def tokens_flops(matmul_params: int, attn_width: int,
                 contexts: Iterable[int]) -> float:
    """FLOPs of one token per entry of ``contexts``: the matmuls, and
    attention of ``attn_width`` (layers x query heads x head_dim) over each
    token's live context."""
    contexts = list(contexts)
    return (2.0 * matmul_params * len(contexts)
            + 4.0 * attn_width * sum(contexts))


def decode_steps_bytes(step_weight_bytes: int, kv_bytes_per_token: int,
                       steps: int, contexts: Iterable[int]) -> float:
    """Bytes of ``steps`` decode steps that read ``step_weight_bytes`` of
    weights each, and the cache of ``contexts`` positions in all."""
    return (float(steps) * step_weight_bytes
            + float(sum(contexts)) * kv_bytes_per_token)


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bytes_s: float) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bytes_s)
