"""Bytes a decode step, and operations a prefill chunk, of an LM of gated
short-convolution layers (a per-slot tail) beside rotary grouped-query
layers (cached K and V rows) with a leading dense layer, routed experts
and a tied head must move and do, from the configuration's sizes and what
the call's routing and slots did.

**A decode step's bytes** (``step_bytes``).  Only what ANY correct
program must move for one token per live slot is counted, each weight
once a step however many slots share it:

* every layer: its mixer's matrices (a convolution layer's ``in_proj``
  and ``out_proj`` and its taps; a grouped-query layer's four matrices
  and the two gains of its q/k norm) and the two norm gains; a dense
  layer's three feed-forward matrices; an expert layer's router matrix
  (bf16, as held) and its bias (float32);
* the routed experts that got at least one pick in the step
  (``experts_hit``, summed over the layers), three matrices each;
* the final norm's gain and the embedding, which is the head;
* the K and V rows the stepped slots hold in the grouped-query layers
  (``kv_rows_held``): ``2 * num_key_value_heads * head_dim`` values each;
* for every stepped slot and convolution layer
  (``state_slots_stepped``) the tail read AND written once (``(taps - 1)
  * hidden_size`` values each way: every token shifts it).

Not counted, though a program may well move them: the embedding rows of
the step's tokens, activations, the new K and V rows written, a block's
rows beyond those held, an idle slot's tail, the logits it hands back.
So the bytes are a floor and ``conv_decode_bytes_roofline`` cannot pass
100%.

**A prefill chunk's operations** (``chunk_flops``), multiply-adds
counted as two, for the chunk's REAL rows: the mixers', routers' and the
dense layer's products; the routed experts by the picks that fell on
held ones; the head for the one row whose logits the chunk returns; the
grouped-query scores and weighted values of each real row over the rows
before it and itself (causal: no more).  Elementwise work (norms, SiLU,
the gates' products, the taps, rotary) is not counted, and a bucket's
padded rows are not: the share of the bf16 peak is a floor in that too.
"""

from __future__ import annotations

# a grouped-query layer's matrices and rows are counted as for the other
# block that reads these keys
from benchmarks.kernels.ssm_decode_bytes import (  # noqa: F401
    CACHE_BYTES, F32, WEIGHT_BYTES, gqa_matrix_params, head_dim, kv_row_bytes)


def layer_counts(c: dict):
    """(grouped-query layers, convolution layers) among the layers held."""
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    return kinds.count("full_attention"), kinds.count("conv")


def ff_counts(c: dict):
    """(dense layers, expert layers) among the layers held."""
    dense = min(c["num_dense_layers"], c["num_hidden_layers"])
    return dense, c["num_hidden_layers"] - dense


def held_experts(c: dict) -> int:
    return c.get("n_routed_experts", c["num_experts"])


def conv_matrix_params(c: dict) -> int:
    d = c["hidden_size"]
    return d * 3 * d + d * d                            # in_proj; out_proj


def mixer_params(c: dict) -> int:
    """Every mixer held: the matrices, a convolution layer's taps, a
    grouped-query layer's two q/k gains."""
    gqa, conv = layer_counts(c)
    return (gqa * (gqa_matrix_params(c) + 2 * head_dim(c))
            + conv * (conv_matrix_params(c)
                      + c["conv_L_cache"] * c["hidden_size"]))


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_ff_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def router_params(c: dict) -> tuple:
    """An expert layer's router: ``(the matrix, held in bf16; the bias,
    in float32)``."""
    return c["hidden_size"] * c["num_experts"], c["num_experts"]


def held_params(c: dict) -> int:
    """Every parameter this chip holds (the configuration's count): the
    embedding once, it being the head too."""
    d = c["hidden_size"]
    dense, moe = ff_counts(c)
    return (mixer_params(c) + c["num_hidden_layers"] * 2 * d
            + dense * dense_ff_params(c)
            + moe * (sum(router_params(c))
                     + held_experts(c) * expert_params(c))
            + d + d * c["vocab_size"])


def fixed_bytes(c: dict) -> int:
    """Weights every step reads whatever the routing: everything but the
    routed experts (the embedding is read once, as the head)."""
    d = c["hidden_size"]
    dense, moe = ff_counts(c)
    matrix, bias = router_params(c)
    params = (mixer_params(c) + c["num_hidden_layers"] * 2 * d
              + dense * dense_ff_params(c) + moe * matrix
              + d + d * c["vocab_size"])
    return params * WEIGHT_BYTES + moe * bias * F32


def tail_bytes(c: dict) -> int:
    """What one slot holds of one convolution layer: its tail."""
    return (c["conv_L_cache"] - 1) * c["hidden_size"] * CACHE_BYTES


def tail_step_bytes(c: dict, state_slots_stepped: float) -> float:
    """The tails' part of a step's floor: read and written once."""
    return 2 * state_slots_stepped * tail_bytes(c)


def expert_step_bytes(c: dict, experts_hit: float) -> float:
    """The hit experts' part of a step's floor."""
    return experts_hit * expert_params(c) * WEIGHT_BYTES


def step_bytes(c: dict, experts_hit: float, kv_rows_held: float,
               state_slots_stepped: float) -> float:
    """All three are what the program counts on ``serve/decode_step``,
    summed over the layers (and slots), for ONE step."""
    return (fixed_bytes(c) + expert_step_bytes(c, experts_hit)
            + kv_rows_held * kv_row_bytes(c)
            + tail_step_bytes(c, state_slots_stepped))


def chunk_flops(c: dict, rows: float, offset: float,
                picks_held: float) -> float:
    """``rows`` real rows at positions ``offset ..`` of one slot, of whose
    routed picks ``picks_held`` fell on held experts (summed over the
    layers)."""
    gqa, conv = layer_counts(c)
    dense, moe = ff_counts(c)
    d = c["hidden_size"]
    h, D = c["num_attention_heads"], head_dim(c)
    products = 2 * rows * (gqa * gqa_matrix_params(c)
                           + conv * conv_matrix_params(c)
                           + dense * dense_ff_params(c)
                           + moe * router_params(c)[0])
    pairs = rows * offset + rows * (rows + 1) / 2.0    # (query, key) causal
    return (products + 2 * picks_held * expert_params(c)
            + 2 * d * c["vocab_size"]
            + gqa * pairs * 4 * h * D)
