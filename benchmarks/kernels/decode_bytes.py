"""Bytes a decode step of a latent-attention / routed-expert LM must
read from device memory, from the configuration's sizes and what the
step's routing did.

Only what ANY correct program must read for one token per live slot is
counted, each weight once a step however many slots share it:

* every layer: the five attention matrices and the four norm gains;
  the leading dense layers' three feed-forward matrices; in an expert
  layer the router (its matrix and float32 bias) and the shared
  expert's three matrices;
* the routed experts that got at least one pick in the step
  (``experts_hit``, summed over the expert layers), three matrices each;
* the final norm's gain and the head;
* the cached latent rows of the live slots' earlier positions, in every
  layer.

Not counted, though a program may well read or write them: the
embedding rows of the step's tokens, activations, the new rows written,
padding of the attention window, an expert read for an idle slot.  So
the bytes are a floor and ``decode_bytes_roofline`` cannot pass 100%.
"""

from __future__ import annotations

WEIGHT_BYTES = 2       # bfloat16, as the configuration states
CACHE_BYTES = 2
F32 = 4


def attention_params(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d
            + 2 * d + c["q_lora_rank"] + c["kv_lora_rank"])   # norm gains


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def fixed_bytes(c: dict) -> int:
    """Weights every step reads whatever the routing: everything but the
    routed experts and the embedding."""
    d = c["hidden_size"]
    layers = c["num_hidden_layers"]
    dense = min(c["first_k_dense_replace"], layers)
    moe = layers - dense
    n_router = c.get("n_routed_experts_published", c["n_routed_experts"])
    params = (layers * attention_params(c)
              + dense * 3 * d * c["intermediate_size"]
              + moe * (d * n_router
                       + c["n_shared_experts"] * expert_params(c))
              + d + d * c["vocab_size"])
    return params * WEIGHT_BYTES + moe * n_router * F32


def latent_row_bytes(c: dict) -> int:
    """One cached token, all layers."""
    return (c["num_hidden_layers"]
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * CACHE_BYTES)


def step_bytes(c: dict, experts_hit: float, cached_rows: float) -> float:
    """``experts_hit``: held experts with at least one pick, summed over
    the expert layers; ``cached_rows``: earlier positions of the live
    slots, summed over the slots."""
    return (fixed_bytes(c) + experts_hit * expert_params(c) * WEIGHT_BYTES
            + cached_rows * latent_row_bytes(c))
