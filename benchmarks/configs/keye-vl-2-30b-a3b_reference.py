"""Plain reference of configuration ``keye-vl-2-30b-a3b``: the language
model of Keye-VL-2.0-30B-A3B (``model_type: KeyeVL2``) in ``jax.numpy``.

float32 at ``highest`` matmul precision; no kernel, no cache, no
batching; imports nothing of the program.  The nine steps of a layer
(``x`` [T, 2048] the float32 residual, ``p_t`` the position of token
``t``; for text the three M-RoPE streams are all ``p_t``):

1. ``h = RMSNorm(x; g1, 1e-6)``.
2. ``q = h Wq`` -> [T, 32, 128]; ``k = h Wk``, ``v = h Wv`` -> [T, 4, 128];
   no biases.
3. ``q <- RMSNorm(q; gq)``, ``k <- RMSNorm(k; gk)`` over each head's 128
   (assumed: the Qwen3-MoE backbone this config matches has them).
4. Rotary on all 128 dims of q and k, rotate-half pairing ``(i, i + 64)``,
   ``inv_freq_i = theta^(-i/64)``; frequency ``i`` takes the temporal
   position for ``i < 16``, the height position for ``16 <= i < 40``, the
   width position for ``40 <= i < 64`` (``mrope_section`` [16, 24, 24]).
5. Indexer (assumed: the published DeepSeek-V3.2-Exp indexer at this
   ``sa_config``'s sizes): ``qI = h WqI`` -> [T, 16, 64];
   ``kI = LayerNorm(h WkI)`` -> [T, 64], ONE key a token; rotary of the
   temporal stream on all 64 dims of both; head weights
   ``w = (h Ww) * 16^-0.5 * 64^-0.5``; score
   ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``.
6. ``S_t`` = the ``min(topk, t + 1)`` positions ``s <= t`` of largest
   ``I[t, s]``: exact, ties to the lower position.
7. Head ``a`` reads KV head ``a // 8``: softmax of ``q . k / sqrt(128)``
   over ``S_t`` only (dense causal scores, the selection as a mask);
   ``x <- x + concat(o) Wo``.
8. ``h2 = RMSNorm(x; g2)``; ``pr = softmax(h2 Wr)`` over the experts; the
   8 largest, weights divided by their sum;
   ``y = sum_e w_e Wdown_e(silu(Wgate_e h2) * (Wup_e h2))``; ``x <- x + y``.
9. After the last layer ``RMSNorm(x; gf)`` and the untied head.

The float32 copy of the cut model is 20 GB, so it is **computed in
blocks**: ``init_layer`` makes one layer's weights from the layer's own
key, in the type the configuration states (bfloat16: the very values
the program holds); ``layer`` widens them and applies the layer to one
sequence, its queries a block at a time; the caller frees them and goes
on.

Controls: the configuration states bf16 weights and cache rows with
float32 accumulation, so the nearest precision below is fp8 operands
(e4m3, one scale a tensor) in every product but the router's, which the
configuration states float32.  ``bfloat16`` is the STATED precision
applied to this reference (every product's operands rounded to bfloat16,
the router's left float32): not a control, which has to fail, but the
second witness of what rounding alone does to the selection and the
routing at the cell's size, with nothing of the program in it
(``benchmarks/probe.py --control-precision bfloat16``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: the sizes the functions here read from a configuration's file
SIZE_KEYS = (
    "vocab_size", "num_hidden_layers", "hidden_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "moe_intermediate_size", "num_experts",
    "n_routed_experts", "first_expert", "num_experts_per_tok", "rms_norm_eps",
    "rope_theta", "rope_scaling", "sa_config", "initializer_range")
PRECISIONS = ("float32", "bfloat16", "fp8")
STATED_PRECISION = "bfloat16"
CONTROL_PRECISION = "fp8"
#: queries whose score matrices are held at once
QUERY_BLOCK = 256


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**32."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def layer_key(key, i: int):
    """Layer ``i``'s own key; -1 the embedding's, -2 the head's."""
    return jax.random.fold_in(key, i + 16)


# -- weights, one block at a time ----------------------------------------------

def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_layer(key, sizes: dict, dense: bool = False,
               dtype=jnp.bfloat16) -> dict:
    """One layer's weights from ITS key: N(0, initializer_range)
    matrices, unit gains, a zero LayerNorm bias.  Every layer is an
    expert layer (``dense`` is never set: ``is_dense``)."""
    assert not dense
    d, D = sizes["hidden_size"], sizes["head_dim"]
    H, KV = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    sa = sizes["sa_config"]
    HI, DI = sa["indexer_num_heads"], sa["indexer_head_dim"]
    f, held = sizes["moe_intermediate_size"], sizes["n_routed_experts"]
    ks = jax.random.split(key, 11)
    N = functools.partial(_normal, std=float(sizes["initializer_range"]),
                          dtype=dtype)
    return {"ln1_g": jnp.ones((d,), dtype), "ln2_g": jnp.ones((d,), dtype),
            "W_q": N(ks[0], (d, H * D)), "W_k": N(ks[1], (d, KV * D)),
            "W_v": N(ks[2], (d, KV * D)), "W_o": N(ks[3], (H * D, d)),
            "q_norm_g": jnp.ones((D,), dtype),
            "k_norm_g": jnp.ones((D,), dtype),
            "W_iq": N(ks[4], (d, HI * DI)), "W_ik": N(ks[5], (d, DI)),
            "ik_norm_g": jnp.ones((DI,), dtype),
            "ik_norm_b": jnp.zeros((DI,), dtype),
            "W_iw": N(ks[6], (d, HI)),
            "router_w": N(ks[7], (d, sizes["num_experts"])),
            "e_gate": N(ks[8], (held, d, f)), "e_up": N(ks[9], (held, d, f)),
            "e_down": N(ks[10], (held, f, d))}


def init_ends(key, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """The embedding, the final norm's gain and the head."""
    d, V = sizes["hidden_size"], sizes["vocab_size"]
    std = float(sizes["initializer_range"])
    return {"embed": _normal(layer_key(key, -1), (V, d), std, dtype),
            "lnf_g": jnp.ones((d,), dtype),
            "head": _normal(layer_key(key, -2), (d, V), std, dtype)}


def is_dense(sizes: dict, i: int) -> bool:
    return False


# -- the lower precision of the control ------------------------------------------

def _fake_quant(x, qdtype=jnp.float8_e4m3fn):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(qdtype).max) / amax, 1.0)
    return (x * scale).astype(qdtype).astype(jnp.float32) / scale


def _ops(precision: str):
    """(matmul, einsum) of ``precision``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    if precision == "float32":
        return jnp.matmul, jnp.einsum
    low = _fake_quant if precision == "fp8" else (
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32))
    return (lambda a, b: jnp.matmul(low(a), low(b)),
            lambda eq, a, b: jnp.einsum(eq, low(a), low(b)))


def with_precision(precision: str):
    """Context under which the reference (or its control) multiplies:
    the control rounds the operands, the products stay exact."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return jax.default_matmul_precision("highest")


# -- the mathematics -----------------------------------------------------------------

def rms_norm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def rope_tables(sizes: dict, positions: np.ndarray):
    """``(cos, sin)`` of the heads [T, head_dim] and of the indexer
    [T, indexer_head_dim] at ``positions`` [3, T] (float64 on the host):
    step 4's streams for the heads, the temporal stream for the indexer."""
    positions = np.asarray(positions, np.float64)
    theta, half = float(sizes["rope_theta"]), int(sizes["head_dim"]) // 2
    section = (sizes.get("rope_scaling") or {}).get("mrope_section") or [half]
    stream = np.repeat(np.arange(len(section)), section)
    ang = positions[stream].T * theta ** (-np.arange(half) / half)
    half_i = int(sizes["sa_config"]["indexer_head_dim"]) // 2
    ang_i = positions[0][:, None] * theta ** (-np.arange(half_i) / half_i)
    two = lambda a: np.concatenate([a, a], axis=-1)
    return tuple(jnp.asarray(f(two(a)), jnp.float32)
                 for a in (ang, ang_i) for f in (np.cos, np.sin))


def rope(x, cos, sin):
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def topk_mask(scores, k: int):
    """Step 6 as a mask: of each row of ``scores`` [n, T] (-inf where a
    position may not be chosen) the ``k`` largest, ties to the lower
    position; every candidate where there are no more than ``k``."""
    kth = -jnp.sort(-scores, axis=-1)[:, min(k, scores.shape[-1]) - 1]
    above = scores > kth[:, None]
    ties = (scores == kth[:, None]) & jnp.isfinite(scores)
    need = k - jnp.sum(above, axis=-1)
    return above | (ties & (jnp.cumsum(ties, axis=-1) <= need[:, None]))


def attention(p, x, sizes, precision="float32", positions=None):
    """Steps 1 to 7 over one sequence ``x`` [T, d].  Returns the
    attention's output after ``Wo`` and the selection [T, T] bool."""
    mm, es = _ops(precision)
    T = x.shape[0]
    H, KV = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    D, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    sa = sizes["sa_config"]
    HI, DI, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    if positions is None:
        positions = np.broadcast_to(np.arange(T), (3, T))
    cos, sin, cos_i, sin_i = rope_tables(sizes, positions)
    h = rms_norm(x, p["ln1_g"], eps)
    q = rms_norm(mm(h, p["W_q"]).reshape(T, H, D), p["q_norm_g"], eps)
    k = rms_norm(mm(h, p["W_k"]).reshape(T, KV, D), p["k_norm_g"], eps)
    v = mm(h, p["W_v"]).reshape(T, KV, D)
    q, k = rope(q, cos[:, None], sin[:, None]), rope(k, cos[:, None],
                                                     sin[:, None])
    q_i = rope(mm(h, p["W_iq"]).reshape(T, HI, DI), cos_i[:, None],
               sin_i[:, None])
    k_i = rope(layer_norm(mm(h, p["W_ik"]), p["ik_norm_g"], p["ik_norm_b"],
                          eps), cos_i, sin_i)
    w = mm(h, p["W_iw"]) * (HI ** -0.5 * DI ** -0.5)
    block = next(b for b in range(min(T, QUERY_BLOCK), 0, -1) if T % b == 0)
    at = jnp.arange(T)

    def rows(t0):
        """A block of queries over the whole sequence."""
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, block)
        seen = at[None, :] <= (t0 + jnp.arange(block))[:, None]
        score = jnp.sum(jax.nn.relu(es("thd,sd->ths", sl(q_i), k_i))
                        * sl(w)[:, :, None], axis=1)
        chosen = topk_mask(jnp.where(seen, score, -jnp.inf), topk)
        s = es("tgad,sgd->gats", sl(q).reshape(block, KV, H // KV, D),
               k) * D ** -0.5
        a = jax.nn.softmax(jnp.where(chosen[None, None], s, -jnp.inf),
                           axis=-1)
        return es("gats,sgd->tgad", a, v).reshape(block, H * D), chosen

    o, chosen = jax.lax.map(rows, jnp.arange(0, T, block))
    return mm(o.reshape(T, H * D), p["W_o"]), chosen.reshape(T, T)


def route(x, router_w, k: int):
    """(ids [T, k], weights [T, k]) of step 8's gate, float32 in every
    precision (the configuration states a float32 router)."""
    w, idx = jax.lax.top_k(jax.nn.softmax(jnp.matmul(x, router_w), axis=-1), k)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True)


def experts(p, x, sizes, precision="float32"):
    """Step 8's sum for ``x`` [T, d] over the experts held (``first_expert
    ..`` of the router's range live in ``p``), one expert after another;
    and the chosen ids [T, k], ascending."""
    mm, _ = _ops(precision)
    idx, w = route(x, p["router_w"], sizes["num_experts_per_tok"])

    def add(y, e):
        gate, up, down, i = e
        w_e = jnp.sum(jnp.where(idx == sizes["first_expert"] + i, w, 0.0),
                      axis=-1)
        return y + w_e[:, None] * mm(jax.nn.silu(mm(x, gate)) * mm(x, up),
                                     down), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(x), (
        p["e_gate"], p["e_up"], p["e_down"],
        jnp.arange(p["e_gate"].shape[0])))
    return y, jnp.sort(idx, axis=-1)


def layer(p, h, sizes, precision="float32", positions=None):
    """One layer over one sequence ``h`` [T, d] float32, from weights in
    any type (widened here).  Returns (h, chosen expert ids [T, k], the
    attention's selection [T, T] bool)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    a, chosen = attention(p, h, sizes, precision, positions)
    h = h + a
    y, picks = experts(p, rms_norm(h, p["ln2_g"], sizes["rms_norm_eps"]),
                       sizes, precision)
    return h + y, picks, chosen


def embed(ends, tokens):
    return ends["embed"].astype(jnp.float32)[tokens]


def logits(ends, h, sizes, precision="float32"):
    mm, _ = _ops(precision)
    return mm(rms_norm(h, ends["lnf_g"].astype(jnp.float32),
                       sizes["rms_norm_eps"]),
              ends["head"].astype(jnp.float32))


def forward(key, tokens, sizes, precision="float32", dtype=jnp.bfloat16,
            positions=None):
    """Logits [T, V], chosen expert ids [layers, T, k] and selections
    [layers, T, T] of one sequence, layer by layer from the seed's key
    (small sizes: the tests' whole-model yardstick)."""
    ends = init_ends(key, sizes, dtype)
    h, picks, chosen = embed(ends, tokens), [], []
    for i in range(int(sizes["num_hidden_layers"])):
        p = init_layer(layer_key(key, i), sizes, dtype=dtype)
        h, pk, ch = layer(p, h, sizes, precision, positions)
        picks.append(pk)
        chosen.append(ch)
    return logits(ends, h, sizes, precision), picks, chosen
