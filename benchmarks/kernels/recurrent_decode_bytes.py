"""Bytes a decode step, and operations a prefill chunk, of an LM whose
layers are of two kinds (grouped-query layers over cached K and V rows,
gated delta-rule linear layers over a per-slot recurrent state) with
routed experts must move and do, from the configuration's sizes and what
the call's routing and slots did.

**A decode step's bytes** (``step_bytes``).  Only what ANY correct
program must move for one token per live slot is counted, each weight
once a step however many slots share it:

* every layer: its mixer's matrices and gains (a grouped-query layer's
  five matrices; a linear layer's four, its two low-rank pairs, ``W_b``,
  the three convolutions' taps, ``A_log`` and ``dt_bias`` in float32 and
  the head norm's gain), the two norm gains, the router (matrix and
  float32 bias) and the shared expert's three matrices;
* the routed experts that got at least one pick in the step
  (``experts_hit``, summed over the layers), three matrices each;
* the final norm's gain and the head;
* the K and V rows the stepped slots hold in the grouped-query layers
  (``kv_rows_held``): ``2 * num_key_value_heads * head_dim`` values each;
* for every stepped slot and linear layer (``state_slots_stepped``) the
  recurrent state read AND written once (``heads * dim * dim`` float32
  values each way: every token replaces it), the convolutions' tail
  likewise.

Not counted, though a program may well move them: the embedding rows of
the step's tokens, activations, the new K and V rows written, a block's
rows beyond those held, an idle slot's, a second read of the state.  So
the bytes are a floor and ``recurrent_decode_bytes_roofline`` cannot pass
100%.

**A prefill chunk's operations** (``chunk_flops``), multiply-adds
counted as two, for the chunk's REAL rows: the mixers', routers' and
shared experts' products; the routed experts by the picks that fell on
held ones; the head for the one row whose logits the chunk returns; the
grouped-query scores and weighted values of each real row over the rows
before it and itself (causal: no more); the linear layers' scan as the
chunked form does it at sub-chunks of ``SUB_CHUNK`` rows (a head a
sub-chunk: the two decayed products and the triangular solve over the
lower half, the products with the carried state).  Elementwise work
(norms, SiLU, the taps, the decays' exponentials) is not counted.  The
scan's products with the state run in float32 on the chip, several bf16
passes each, and count once here: the share of the bf16 peak is a floor
in that too.
"""

from __future__ import annotations

WEIGHT_BYTES = 2       # bfloat16, as the configuration states
CACHE_BYTES = 2
F32 = 4
SUB_CHUNK = 64         # rows of a sub-chunk of the scan


def _linear(c: dict):
    la = c["linear_attn_config"]
    return la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]


def layer_counts(c: dict):
    """(grouped-query layers, linear layers) among the layers held."""
    gqa = sum(1 for i in c["gqa_layers"] if i < c["num_hidden_layers"])
    return gqa, c["num_hidden_layers"] - gqa


def gqa_matrix_params(c: dict) -> int:
    d, D = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return 3 * d * h * D + 2 * d * kv * D               # Wq, Wg, Wo; Wk, Wv


def linear_matrix_params(c: dict) -> int:
    d = c["hidden_size"]
    n, dl, _ = _linear(c)
    return 4 * d * n * dl + 2 * (d * dl + dl * n * dl) + d * n


def linear_vector_bytes(c: dict) -> int:
    """A linear layer's taps and head norm's gain (bf16), ``A_log`` and
    ``dt_bias`` (float32)."""
    n, dl, taps = _linear(c)
    return (taps * 3 * n * dl + dl) * WEIGHT_BYTES + (n + n * dl) * F32


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: dict) -> int:
    """A layer's router matrix and shared expert."""
    return (c["hidden_size"] * c["n_routed_experts_published"]
            + c["n_shared_experts"] * expert_params(c))


def held_params(c: dict) -> int:
    """Every parameter this chip holds (the configuration's count)."""
    gqa, lin = layer_counts(c)
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    n, dl, taps = _linear(c)
    return (gqa * gqa_matrix_params(c)
            + lin * (linear_matrix_params(c) + taps * 3 * n * dl + dl + n
                     + n * dl)
            + layers * (shared_params(c) + c["n_routed_experts_published"]
                        + c["n_routed_experts"] * expert_params(c) + 2 * d)
            + d + 2 * d * c["vocab_size"])


def fixed_bytes(c: dict) -> int:
    """Weights every step reads whatever the routing: everything but the
    routed experts and the embedding."""
    gqa, lin = layer_counts(c)
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    params = (gqa * gqa_matrix_params(c) + lin * linear_matrix_params(c)
              + layers * (shared_params(c) + 2 * d) + d + d * c["vocab_size"])
    return (params * WEIGHT_BYTES + lin * linear_vector_bytes(c)
            + layers * c["n_routed_experts_published"] * F32)


def kv_row_bytes(c: dict) -> int:
    """One token's K and V rows, one grouped-query layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * CACHE_BYTES


def state_bytes(c: dict) -> int:
    """What one slot holds of one linear layer: the state in float32 and
    the convolutions' tail."""
    n, dl, taps = _linear(c)
    return n * dl * dl * F32 + (taps - 1) * 3 * n * dl * CACHE_BYTES


def state_step_bytes(c: dict, state_slots_stepped: float) -> float:
    """The state's part of a step's floor: read and written once."""
    return 2 * state_slots_stepped * state_bytes(c)


def step_bytes(c: dict, experts_hit: float, kv_rows_held: float,
               state_slots_stepped: float) -> float:
    """All three are what the program counts on ``serve/decode_step``,
    summed over the layers (and slots), for ONE step."""
    return (fixed_bytes(c) + experts_hit * expert_params(c) * WEIGHT_BYTES
            + kv_rows_held * kv_row_bytes(c)
            + state_step_bytes(c, state_slots_stepped))


def scan_flops_per_row(c: dict) -> float:
    """The chunked scan's products, a row a linear layer (all heads)."""
    n, dl, _ = _linear(c)
    half = (SUB_CHUNK + 1) / 2.0           # columns of a lower triangle, a row
    per_head = 2 * (2 * half * dl          # the two decayed products
                    + half * 2 * dl        # the solve against [V | Kbar]
                    + 3 * dl * dl          # Xk S, Qbar S, Ktil^T W
                    + half * dl)           # B W
    return n * per_head


def chunk_flops(c: dict, rows: float, offset: float, picks_held: float
                ) -> float:
    """``rows`` real rows at positions ``offset ..`` of one slot, of whose
    routed picks ``picks_held`` fell on held experts (summed over the
    layers)."""
    gqa, lin = layer_counts(c)
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    h, D = c["num_attention_heads"], c["head_dim"]
    dense = 2 * rows * (gqa * gqa_matrix_params(c)
                        + lin * linear_matrix_params(c)
                        + layers * shared_params(c))
    pairs = rows * offset + rows * (rows + 1) / 2.0    # (query, key) causal
    return (dense + 2 * picks_held * expert_params(c)
            + 2 * d * c["vocab_size"]
            + gqa * pairs * 4 * h * D
            + lin * rows * scan_flops_per_row(c))
