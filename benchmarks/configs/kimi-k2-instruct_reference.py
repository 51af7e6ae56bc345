"""Plain reference of configuration ``kimi-k2-instruct``: the chip's
share of Kimi-K2-Instruct (``model_type: kimi_k2``, whose published
description is DeepSeek-V3's modelling code) in ``jax.numpy``.

float32 at ``highest`` matmul precision; no kernel, no cache, no
batching; imports nothing of the program.  The equations:

* layer: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``;
  a final RMSNorm, then the (untied) head;
* attention (MLA) by the EXPANDED path only: ``c_q = RMSNorm(u W_qa)``,
  ``[q_nope | q_pe] = c_q W_qb`` per head, ``[c_kv | k_pe] = u W_kva``,
  ``c_kv = RMSNorm(c_kv)``, one rotary key for all heads,
  ``[k_nope | v] = c_kv W_kvb`` per head, causal softmax of
  ``(q_nope . k_nope + q_pe . k_pe) * 192^-0.5 * m^2`` with
  ``m = 0.1 * mscale_all_dim * ln(factor) + 1``;
* rotary: YaRN frequencies as the published code blends them (linear
  ramp between its two correction bounds), interleaved pairs gathered
  into halves before ``rotate_half``, cos/sin times
  ``mscale / mscale_all_dim``;
* feed-forward of the leading dense layer and of every expert, shared
  or routed: ``W_down(silu(W_gate x) * (W_up x))``;
* router (``noaux_tc``, one group): ``s = sigmoid(x W_g)``; the 8
  experts are the top 8 of ``s + b``; weights ``s`` of those 8 over
  their sum (+1e-20) times ``routed_scaling_factor``;
  ``y = sum_i w_i Expert_i(x) + Shared(x)``.

The share: the router routes over all 384 experts; this chip holds the
``n_routed_experts`` of the file from ``first_expert`` on, and a pick
on an expert that lives elsewhere contributes nothing (a loop over the
held experts, each weighted by the router's weight where it was picked
and 0 elsewhere).  The vocabulary is the file's slice.

The float32 copy of the cut model is 22 GB, so it is **computed in
blocks**: ``init_layer`` makes one layer's weights from the layer's own
key, in the type the configuration states (bfloat16: the very values
the program holds); ``layer`` widens them and applies the layer to
every compared sequence; the caller frees them and goes on.

Controls: the configuration states bf16 weights and cache with float32
accumulation, so the nearest precision below is fp8 operands (e4m3, one
scale a tensor) in every product but the router's, which the
configuration states float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: the sizes the functions here read from a configuration's file
SIZE_KEYS = (
    "vocab_size", "num_hidden_layers", "hidden_size", "num_attention_heads",
    "intermediate_size", "moe_intermediate_size", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "n_routed_experts", "n_routed_experts_published", "first_expert",
    "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
    "routed_scaling_factor", "rms_norm_eps", "rope_theta", "rope_scaling",
    "initializer_range", "router_bias_std")
PRECISIONS = ("float32", "fp8")
STATED_PRECISION = "bfloat16"
CONTROL_PRECISION = "fp8"
#: heads whose score matrices are held at once
HEAD_GROUP = 8


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


def init_layer(key, sizes: dict, dense: bool, dtype=jnp.bfloat16) -> dict:
    """One layer's weights from ITS key: N(0, initializer_range)
    matrices, unit gains, the router's correction bias
    N(0, router_bias_std) in float32 (non-zero, so the choice-only path
    is worked; the configuration's file says why it is small)."""
    d, H = sizes["hidden_size"], sizes["num_attention_heads"]
    c, r = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    n, v = sizes["qk_nope_head_dim"], sizes["v_head_dim"]
    ql, std = sizes["q_lora_rank"], float(sizes["initializer_range"])
    ks = jax.random.split(key, 16)
    N = functools.partial(_normal, std=std, dtype=dtype)
    p = {"ln1_g": jnp.ones((d,), dtype), "ln2_g": jnp.ones((d,), dtype),
         "W_qa": N(ks[0], (d, ql)), "q_norm_g": jnp.ones((ql,), dtype),
         "W_qb": N(ks[1], (ql, H * (n + r))),
         "W_kva": N(ks[2], (d, c + r)), "kv_norm_g": jnp.ones((c,), dtype),
         "W_kvb": N(ks[3], (c, H * (n + v))),
         "W_o": N(ks[4], (H * v, d))}
    if dense:
        f = sizes["intermediate_size"]
        p.update(W_gate=N(ks[5], (d, f)), W_up=N(ks[6], (d, f)),
                 W_down=N(ks[7], (f, d)))
        return p
    f, held = sizes["moe_intermediate_size"], sizes["n_routed_experts"]
    p.update(router_w=N(ks[8], (d, sizes["n_routed_experts_published"])),
             router_b=float(sizes["router_bias_std"]) * jax.random.normal(
                 ks[9], (sizes["n_routed_experts_published"],), jnp.float32),
             e_gate=N(ks[10], (held, d, f)), e_up=N(ks[11], (held, d, f)),
             e_down=N(ks[12], (held, f, d)))
    fs = sizes["n_shared_experts"] * f
    if fs:
        p.update(s_gate=N(ks[13], (d, fs)), s_up=N(ks[14], (d, fs)),
                 s_down=N(ks[15], (fs, d)))
    return p


def init_ends(key, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """The embedding, the final norm's gain and the head."""
    d, V = sizes["hidden_size"], sizes["vocab_size"]
    std = float(sizes["initializer_range"])
    return {"embed": _normal(layer_key(key, -1), (V, d), std, dtype),
            "lnf_g": jnp.ones((d,), dtype),
            "head": _normal(layer_key(key, -2), (d, V), std, dtype)}


def is_dense(sizes: dict, i: int) -> bool:
    return i < int(sizes["first_k_dense_replace"])


# -- the lower precision of the control ------------------------------------------

def _fake_quant(x, qdtype=jnp.float8_e4m3fn):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(qdtype).max) / amax, 1.0)
    return (x * scale).astype(qdtype).astype(jnp.float32) / scale


def _ops(precision: str):
    """(matmul, einsum) of ``precision``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    if precision == "fp8":
        return (lambda a, b: jnp.matmul(_fake_quant(a), _fake_quant(b)),
                lambda eq, a, b: jnp.einsum(eq, _fake_quant(a),
                                            _fake_quant(b)))
    return jnp.matmul, jnp.einsum


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


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(sizes: dict) -> np.ndarray:
    dim, base = int(sizes["qk_rope_head_dim"]), float(sizes["rope_theta"])
    rs = sizes.get("rope_scaling") or {}
    pos_freq = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if float(rs.get("factor", 1.0)) <= 1.0:
        return 1.0 / pos_freq
    extra, inter = 1.0 / pos_freq, 1.0 / (float(rs["factor"]) * pos_freq)
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001                      # the published code's widening
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def rope_tables(sizes: dict, n_pos: int):
    ang = np.outer(np.arange(n_pos, dtype=np.float64), yarn_inv_freq(sizes))
    emb = np.concatenate([ang, ang], axis=-1)
    rs = sizes.get("rope_scaling") or {}
    f = float(rs.get("factor", 1.0))
    m = yarn_mscale(f, rs.get("mscale", 1.0)) \
        / yarn_mscale(f, rs.get("mscale_all_dim", 0.0))
    return (jnp.asarray(np.cos(emb) * m, jnp.float32),
            jnp.asarray(np.sin(emb) * m, jnp.float32))


def softmax_scale(sizes: dict) -> float:
    s = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]) ** -0.5
    rs = sizes.get("rope_scaling") or {}
    f = float(rs.get("factor", 1.0))
    if f > 1.0:
        s *= yarn_mscale(f, rs.get("mscale_all_dim", 0.0)) ** 2
    return s


def rope(x, cos, sin):
    r = x.shape[-1]
    x = jnp.swapaxes(x.reshape(x.shape[:-1] + (r // 2, 2)), -1, -2)
    x = x.reshape(x.shape[:-2] + (r,))
    rot = jnp.concatenate([-x[..., r // 2:], x[..., : r // 2]], axis=-1)
    return x * cos + rot * sin


def attention(p, x, sizes, precision="float32"):
    """MLA over one sequence ``x`` [T, d], keys and values expanded."""
    mm, es = _ops(precision)
    T = x.shape[0]
    H, c = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    n, r, v = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
               sizes["v_head_dim"])
    eps = sizes["rms_norm_eps"]
    cos, sin = rope_tables(sizes, T)
    u = rms_norm(x, p["ln1_g"], eps)
    q = mm(rms_norm(mm(u, p["W_qa"]), p["q_norm_g"], eps),
           p["W_qb"]).reshape(T, H, n + r)
    q_nope, q_pe = q[..., :n], rope(q[..., n:], cos[:, None], sin[:, None])
    kva = mm(u, p["W_kva"])
    c_kv = rms_norm(kva[:, :c], p["kv_norm_g"], eps)
    k_pe = rope(kva[:, c:], cos, sin)
    kv = mm(c_kv, p["W_kvb"]).reshape(T, H, n + v)
    k_nope, val = kv[..., :n], kv[..., n:]
    causal = jnp.tril(jnp.ones((T, T), bool))
    scale = softmax_scale(sizes)
    outs = []
    for g in range(0, H, HEAD_GROUP):       # a few heads' scores at a time
        sl = slice(g, g + HEAD_GROUP)
        s = (es("thn,lhn->htl", q_nope[:, sl], k_nope[:, sl])
             + es("thr,lr->htl", q_pe[:, sl], k_pe)) * scale
        a = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
        outs.append(es("htl,lhv->thv", a, val[:, sl]))
    o = jnp.concatenate(outs, axis=1).reshape(T, H * v)
    return mm(o, p["W_o"])


def gated_silu(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def route(x, router_w, router_b, k: int, scaling: float):
    """(ids [T, k], weights [T, k]) of the ``noaux_tc`` gate, float32
    in every precision (the configuration states a float32 router)."""
    s = jax.nn.sigmoid(jnp.matmul(x, router_w))
    _, idx = jax.lax.top_k(s + router_b, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scaling


def experts(p, x, sizes, precision="float32", first_expert=None,
            with_shared=True):
    """The held experts' part of the layer for ``x`` [T, d] (experts
    ``first_expert ..`` of the router's range live in ``p``), plus the
    shared expert; and the chosen ids [T, k], ascending."""
    mm, _ = _ops(precision)
    first = sizes["first_expert"] if first_expert is None else first_expert
    idx, w = route(x, p["router_w"], p["router_b"],
                   sizes["num_experts_per_tok"],
                   sizes["routed_scaling_factor"])
    y = jnp.zeros_like(x)
    for e in range(p["e_gate"].shape[0]):   # a loop over the experts held
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * gated_silu(x, p["e_gate"][e], p["e_up"][e],
                                          p["e_down"][e], mm)
    if with_shared and "s_gate" in p:
        y = y + gated_silu(x, p["s_gate"], p["s_up"], p["s_down"], mm)
    return y, jnp.sort(idx, axis=-1)


def layer(p, h, sizes, precision="float32"):
    """One layer over one sequence ``h`` [T, d] float32, from weights in
    any type (widened here).  Returns (h, chosen ids [T, k] or None)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    mm, _ = _ops(precision)
    h = h + attention(p, h, sizes, precision)
    u = rms_norm(h, p["ln2_g"], sizes["rms_norm_eps"])
    if "W_gate" in p:
        return h + gated_silu(u, p["W_gate"], p["W_up"], p["W_down"], mm), None
    y, picks = experts(p, u, sizes, precision)
    return h + y, picks


def embed(ends, tokens):
    return ends["embed"].astype(jnp.float32)[tokens]


def logits(ends, h, sizes, precision="float32"):
    mm, _ = _ops(precision)
    return mm(rms_norm(h, ends["lnf_g"].astype(jnp.float32),
                       sizes["rms_norm_eps"]),
              ends["head"].astype(jnp.float32))


def forward(key, tokens, sizes, precision="float32", dtype=jnp.bfloat16):
    """Logits [T, V] and chosen ids [expert layers, T, k] of one
    sequence, layer by layer from the seed's key (small sizes: the
    tests' whole-model yardstick)."""
    ends = init_ends(key, sizes, dtype)
    h, picks = embed(ends, tokens), []
    for i in range(int(sizes["num_hidden_layers"])):
        p = init_layer(layer_key(key, i), sizes, is_dense(sizes, i), dtype)
        h, pk = layer(p, h, sizes, precision)
        if pk is not None:
            picks.append(pk)
    return logits(ends, h, sizes, precision), picks
