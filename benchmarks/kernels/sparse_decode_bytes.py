"""Bytes a decode step of a grouped-query / routed-expert LM whose
attention reads a learned selection of its cache must read from device
memory, from the configuration's sizes and what the step's routing and
selection did.

Only what ANY correct program must read for one token per live slot is
counted, each weight once a step however many slots share it:

* every layer: the four attention matrices, the indexer's three, the
  norm gains (two of the layer, two of the heads, the index key's gain
  and bias) and the router's matrix;
* the routed experts that got at least one pick in the step
  (``experts_hit``, summed over the layers), three matrices each;
* the final norm's gain and the head;
* the index keys the indexer scored (``index_rows_scored``, summed over
  the layers and slots): ``indexer_head_dim`` values each;
* the K and V rows attention read after the selection
  (``attn_rows_read``, likewise): ``2 * num_key_value_heads * head_dim``
  values each.  The program counts what its calls did (whole blocks of
  index rows up to the fullest slot's, the gather of an idle slot), so
  the reader hands in no more of either than the rows held
  (``rows_held``): no program need read a row twice or one not held.

Not counted, though a program may well read or write them: the
embedding rows of the step's tokens, activations, the new rows written,
the lanes an index key's row is padded to, an expert read for an idle
slot.  So the bytes are a floor and ``sparse_decode_bytes_roofline``
cannot pass 100%.
"""

from __future__ import annotations

WEIGHT_BYTES = 2       # bfloat16, as the configuration states
CACHE_BYTES = 2


def attention_params(c: dict) -> int:
    d, D = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return (d * h * D + 2 * d * kv * D + h * D * d      # Wq, Wk, Wv, Wo
            + 2 * d + 2 * D)                            # norm gains


def indexer_params(c: dict) -> int:
    d, sa = c["hidden_size"], c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return d * hi * di + d * di + d * hi + 2 * di       # WqI, WkI, Ww, LayerNorm


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def fixed_bytes(c: dict) -> int:
    """Weights every step reads whatever the routing: everything but the
    routed experts and the embedding."""
    d = c["hidden_size"]
    params = (c["num_hidden_layers"] * (attention_params(c)
                                        + indexer_params(c)
                                        + d * c["num_experts"])
              + d + d * c["vocab_size"])
    return params * WEIGHT_BYTES


def index_row_bytes(c: dict) -> int:
    """One scored index key, one layer."""
    return c["sa_config"]["indexer_head_dim"] * CACHE_BYTES


def kv_row_bytes(c: dict) -> int:
    """One token's K and V rows, one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * CACHE_BYTES


def step_bytes(c: dict, experts_hit: float, index_rows_scored: float,
               attn_rows_read: float) -> float:
    """All three are what the program counts on ``serve/decode_step``,
    summed over the layers (and slots), for ONE step."""
    return (fixed_bytes(c) + experts_hit * expert_params(c) * WEIGHT_BYTES
            + index_rows_scored * index_row_bytes(c)
            + attn_rows_read * kv_row_bytes(c))
