"""Bytes a decode step, and operations a prefill chunk, of an LM of
Mamba-2 state-space layers (a per-slot state) beside ungated
grouped-query layers (cached K and V rows) with routed experts, a shared
MLP and a tied head must move and do, from the configuration's sizes and
what the call's routing and slots did.

**A decode step's bytes** (``step_bytes``).  Only what ANY correct
program must move for one token per live slot is counted, each weight
once a step however many slots share it:

* every layer: its mixer's matrices (a state-space layer's ``in_proj``
  and ``out_proj``, its taps, their bias and the gated norm's gain in
  bf16, ``A_log``, ``D`` and ``dt_bias`` in float32; a grouped-query
  layer's four matrices), the two norm gains, the router's matrix and
  the shared MLP's three matrices;
* the routed experts that got at least one pick in the step
  (``experts_hit``, summed over the layers), three matrices each;
* the final norm's gain and the held slice of the embedding, which is
  the head;
* the K and V rows the stepped slots hold in the grouped-query layers
  (``kv_rows_held``): ``2 * num_key_value_heads * head_dim`` values each;
* for every stepped slot and state-space layer
  (``state_slots_stepped``) the state read AND written once (``heads *
  d_head * d_state`` float32 values each way: every token replaces it),
  the convolution's tail likewise.

Not counted, though a program may well move them: the embedding rows of
the step's tokens, activations, the new K and V rows written, a block's
rows beyond those held, an idle slot's state.  So the bytes are a floor
and ``ssm_decode_bytes_roofline`` cannot pass 100%.

**A prefill chunk's operations** (``chunk_flops``), multiply-adds
counted as two, for the chunk's REAL rows: the mixers', routers' and
shared MLPs' products; the routed experts by the picks that fell on held
ones; the head for the one row whose logits the chunk returns; the
grouped-query scores and weighted values of each real row over the rows
before it and itself (causal: no more); the state-space layers' scan as
the chunked form does it at chunks of ``mamba_chunk_size`` rows (``C
B^T`` once for all heads and a head's ``(G * L)(dt x)``, both over the
lower triangle of the real rows of a chunk only; the product with the
carried state and the state's update, a row each).  Elementwise work
(norms, SiLU, the taps, the decays' exponentials) is not counted, and a
bucket's padded rows are not.  The scan's products run in float32 on the
chip, several bf16 passes each, and count once here: the share of the
bf16 peak is a floor in that too.
"""

from __future__ import annotations

WEIGHT_BYTES = 2       # bfloat16, as the configuration states
CACHE_BYTES = 2
F32 = 4


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def inner(c: dict) -> int:
    return c["mamba_n_heads"] * c["mamba_d_head"]


def conv_dim(c: dict) -> int:
    return inner(c) + 2 * c.get("mamba_n_groups", 1) * c["mamba_d_state"]


def layer_counts(c: dict):
    """(grouped-query layers, state-space layers) among the layers held."""
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    return kinds.count("attention"), kinds.count("mamba")


def gqa_matrix_params(c: dict) -> int:
    d, D = c["hidden_size"], head_dim(c)
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * h * D + 2 * d * kv * D               # Wq, Wo; Wk, Wv


def mamba_matrix_params(c: dict) -> int:
    d = c["hidden_size"]
    return d * (inner(c) + conv_dim(c) + c["mamba_n_heads"]) + inner(c) * d


def mamba_vector_params(c: dict) -> tuple:
    """A state-space layer's small leaves: ``(taps, their bias and the
    gated norm's gain, held in bf16; A_log, D and dt_bias, in float32)``."""
    rows = c["mamba_d_conv"] + (1 if c.get("mamba_conv_bias", True) else 0)
    return rows * conv_dim(c) + inner(c), 3 * c["mamba_n_heads"]


def mamba_vector_bytes(c: dict) -> int:
    low, high = mamba_vector_params(c)
    return low * WEIGHT_BYTES + high * F32


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def shared_params(c: dict) -> int:
    """A layer's router matrix and shared MLP."""
    return c["hidden_size"] * (c["num_local_experts_published"]
                               + 3 * c["shared_intermediate_size"])


def held_params(c: dict) -> int:
    """Every parameter this chip holds (the configuration's count): the
    embedding once, it being the head too."""
    gqa, ssm = layer_counts(c)
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    return (gqa * gqa_matrix_params(c)
            + ssm * (mamba_matrix_params(c) + sum(mamba_vector_params(c)))
            + layers * (shared_params(c)
                        + c["num_local_experts"] * expert_params(c) + 2 * d)
            + d + d * c["vocab_size"])


def fixed_bytes(c: dict) -> int:
    """Weights every step reads whatever the routing: everything but the
    routed experts (the embedding's slice is read once, as the head)."""
    gqa, ssm = layer_counts(c)
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    params = (gqa * gqa_matrix_params(c) + ssm * mamba_matrix_params(c)
              + layers * (shared_params(c) + 2 * d) + d + d * c["vocab_size"])
    return params * WEIGHT_BYTES + ssm * mamba_vector_bytes(c)


def kv_row_bytes(c: dict) -> int:
    """One token's K and V rows, one grouped-query layer."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * CACHE_BYTES


def state_bytes(c: dict) -> int:
    """What one slot holds of one state-space layer: the state in
    float32 and the convolution's tail."""
    return (c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"] * F32
            + (c["mamba_d_conv"] - 1) * conv_dim(c) * CACHE_BYTES)


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


def scan_pairs(rows: float, chunk: int) -> float:
    """(row, earlier-or-same row) pairs inside the chunks of ``chunk``
    rows that ``rows`` real rows fill from the first on."""
    whole, rest = divmod(int(rows), chunk)
    return whole * chunk * (chunk + 1) / 2.0 + rest * (rest + 1) / 2.0


def scan_flops(c: dict, rows: float, chunk: int) -> float:
    """The chunked scan's products for ``rows`` real rows of one
    state-space layer (all heads) at chunks of ``chunk`` rows."""
    hp, n = inner(c), c["mamba_d_state"]
    return (scan_pairs(rows, chunk) * 2 * (n + hp)     # C B^T; (G * L)(dt x)
            + rows * 2 * 2 * hp * n)                   # C S; the state's update


def chunk_flops(c: dict, rows: float, offset: float, picks_held: float,
                bucket: float) -> float:
    """``rows`` real rows at positions ``offset ..`` of one slot in a
    bucket of ``bucket`` rows, of whose routed picks ``picks_held`` fell
    on held experts (summed over the layers)."""
    gqa, ssm = layer_counts(c)
    d = c["hidden_size"]
    h, D = c["num_attention_heads"], head_dim(c)
    dense = 2 * rows * (gqa * gqa_matrix_params(c)
                        + ssm * mamba_matrix_params(c)
                        + c["num_hidden_layers"] * shared_params(c))
    pairs = rows * offset + rows * (rows + 1) / 2.0    # (query, key) causal
    chunk = int(min(c.get("mamba_chunk_size", 256), bucket))
    return (dense + 2 * picks_held * expert_params(c)
            + 2 * d * c["vocab_size"]
            + gqa * pairs * 4 * h * D
            + ssm * scan_flops(c, rows, chunk))
