"""Plain reference of configuration ``solar-open2-250b``: the chip's share
of Solar-Open2-250B's language model (``model_type: solar_open2``) in
``jax.numpy``.

float32 at ``highest`` matmul precision; no kernel, no cache, no chunked
form, no batching; imports nothing of the program.  The equations
(``u = RMSNorm(x)``, eps ``rms_norm_eps``; items marked *assumed* are
argued in the configuration's file under ``assumed``):

* every layer: ``h = x + Mix(u)``, ``y = h + Experts(RMSNorm(h))``; a
  final RMSNorm, then the (untied) head;
* ``Experts``: router over all 320, top 8, weights normalised over the 8
  (``norm_topk_prob``), times ``routed_scaling_factor`` 1, plus one
  shared expert, every expert ``W_down(silu(W_gate x) * (W_up x))``
  without biases.  *Assumed*: sigmoid scores with a correction bias added
  for the choice only (``noaux_tc``, one group), the bias N(0,
  ``router_bias_std``);
* ``Mix`` at a ``gqa_layers`` index: ``q = u W_q`` (64 heads of 128),
  ``k = u W_k``, ``v = u W_v`` (8 heads of 128; query head ``a`` reads KV
  head ``a // 8``), NO rotary and no other position (``use_rope`` false),
  causal softmax at scale ``128^-0.5`` over every earlier row, ``o = W_o
  (sigmoid(u W_g) * att)``.  *Assumed*: the gate is one value a channel
  from the normed input; no q/k norm;
* ``Mix`` elsewhere (gated delta rule with a decay a channel): ``q, k, v
  = SiLU(conv4(u W_q)), SiLU(conv4(u W_k)), SiLU(conv4(u W_v))``, a causal
  depthwise convolution of 4 taps a channel without bias; ``q``, ``k``
  L2-normalised a head, ``q`` times ``128^-0.5``; ``g_t = -exp(A_h)
  softplus(u W_f1 W_f2 + b_dt)`` a key channel, ``alpha_t = exp(g_t)``;
  ``beta_t = 2 sigmoid(u W_b)`` a head (``kda_allow_neg_eigval``); a
  state ``S`` [128 x 128] a head:

      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  then ``out = W_o (RMSNorm_head(o_t) * sigmoid(u W_g1 W_g2))``.
  *Assumed*: ``kda_use_full_proj`` false reads as the low-rank pairs
  ``W_f1, W_g1`` [4096 x 128] and ``W_f2, W_g2`` [128 x 8192]; ``A_h =
  log U(1, 16)`` a head and ``b_dt`` with ``softplus(b_dt)`` log-uniform
  in [0.001, 0.1]; the taps U(-0.5, 0.5); the head norm's gain one.

The linear layers run **token by token** by the recurrence as written (a
``lax.scan`` over the positions whose carry is ``S``).  The grouped-query
attention is blocked over the query positions only so that its scores
fit.

The share: the router routes over all 320 experts; this chip holds the
``n_routed_experts`` of the file from ``first_expert`` on, and a pick on
an expert that lives elsewhere contributes nothing (a loop over the held
experts, each weighted by the router's weight where it was picked and 0
elsewhere).  The vocabulary is the file's slice.

It is **computed in blocks**: ``init_layer`` makes one layer's weights
from the layer's own key, in the type the configuration states
(bfloat16: the very values the program holds); ``layer`` widens them and
applies the layer to one compared sequence; the caller frees them and
goes on.

Precisions.  ``float32`` is the reference.  The configuration states
bfloat16 weights, K/V rows and convolution tails with float32
accumulation, a float32 router and a float32 recurrent state:
``bfloat16`` is the reference AT that stated precision, with nothing of
the program in it: both operands of every product but the router's and
the state's rounded to bfloat16 (the product itself exact, accumulated
in float32), the convolutions' inputs rounded as the tail a slot keeps
is, the state and every product with it float32.  It is what the
state's precision is read against: the operands' rounding, which moves
the logits further than the state's does, is common to both sides.
The nearest precisions below, the controls: ``fp8`` operands (e4m3, one
scale a tensor) in every product but the router's; and ``bf16_state``,
the stated precision with the recurrent state rounded to bfloat16
after every token where float32 is stated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: the sizes the functions here read from a configuration's file
SIZE_KEYS = (
    "vocab_size", "num_hidden_layers", "hidden_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "linear_attn_config", "gqa_layers",
    "moe_intermediate_size", "n_routed_experts",
    "n_routed_experts_published", "first_expert", "num_experts_per_tok",
    "n_shared_experts", "routed_scaling_factor", "rms_norm_eps",
    "initializer_range", "router_bias_std")
PRECISIONS = ("float32", "bfloat16", "fp8", "bf16_state")
#: those whose operands are the stated bfloat16
STATED = ("bfloat16", "bf16_state")
STATED_PRECISION = "bfloat16"
CONTROL_PRECISION = "fp8"
#: query rows whose scores are held at once
QUERY_BLOCK = 256


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**32."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def layer_key(key, i: int):
    """Layer ``i``'s own key; -1 the embedding's, -2 the head's."""
    return jax.random.fold_in(key, i + 16)


def layer_kind(sizes: dict, i: int) -> str:
    """``"gqa"`` at a ``gqa_layers`` index, ``"linear"`` elsewhere."""
    return "gqa" if i in set(sizes["gqa_layers"]) else "linear"


# -- weights, one block at a time ----------------------------------------------

def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_layer(key, sizes: dict, kind: str, dtype=jnp.bfloat16) -> dict:
    """One layer's weights from ITS key: N(0, initializer_range) matrices,
    unit gains, the router's correction bias N(0, router_bias_std) in
    float32; of a linear layer also the taps U(-0.5, 0.5), ``A_log`` and
    ``dt_bias`` (float32) as the docstring draws them."""
    d, std = sizes["hidden_size"], float(sizes["initializer_range"])
    ks = jax.random.split(key, 24)
    N = functools.partial(_normal, std=std, dtype=dtype)
    p = {"ln1_g": jnp.ones((d,), dtype), "ln2_g": jnp.ones((d,), dtype)}
    if kind == "gqa":
        H, KV = sizes["num_attention_heads"], sizes["num_key_value_heads"]
        D = sizes["head_dim"]
        p.update(W_q=N(ks[0], (d, H * D)), W_k=N(ks[1], (d, KV * D)),
                 W_v=N(ks[2], (d, KV * D)), W_g=N(ks[3], (d, H * D)),
                 W_o=N(ks[4], (H * D, d)))
    else:
        la = sizes["linear_attn_config"]
        n, dl, taps = la["num_heads"], la["head_dim"], \
            la["short_conv_kernel_size"]
        c = n * dl
        dt = jnp.exp(jax.random.uniform(ks[10], (c,), jnp.float32,
                                        jnp.log(0.001), jnp.log(0.1)))
        p.update(
            W_q=N(ks[0], (d, c)), W_k=N(ks[1], (d, c)), W_v=N(ks[2], (d, c)),
            W_o=N(ks[4], (c, d)),
            conv_w=jax.random.uniform(ks[5], (taps, 3 * c), jnp.float32,
                                      -0.5, 0.5).astype(dtype),
            W_f1=N(ks[6], (d, dl)), W_f2=N(ks[7], (dl, c)),
            W_g1=N(ks[8], (d, dl)), W_g2=N(ks[9], (dl, c)),
            W_b=N(ks[11], (d, n)),
            A_log=jnp.log(jax.random.uniform(ks[12], (n,), jnp.float32,
                                             1.0, 16.0)),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1(dt)
            o_norm_g=jnp.ones((dl,), dtype))
    f, held = sizes["moe_intermediate_size"], sizes["n_routed_experts"]
    width = sizes["n_routed_experts_published"]
    p.update(router_w=N(ks[13], (d, width)),
             router_b=float(sizes["router_bias_std"]) * jax.random.normal(
                 ks[14], (width,), jnp.float32),
             e_gate=N(ks[15], (held, d, f)), e_up=N(ks[16], (held, d, f)),
             e_down=N(ks[17], (held, f, d)))
    fs = sizes["n_shared_experts"] * f
    if fs:
        p.update(s_gate=N(ks[18], (d, fs)), s_up=N(ks[19], (d, fs)),
                 s_down=N(ks[20], (fs, d)))
    return p


def init_ends(key, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """The embedding, the final norm's gain and the head."""
    d, V = sizes["hidden_size"], sizes["vocab_size"]
    std = float(sizes["initializer_range"])
    return {"embed": _normal(layer_key(key, -1), (V, d), std, dtype),
            "lnf_g": jnp.ones((d,), dtype),
            "head": _normal(layer_key(key, -2), (d, V), std, dtype)}


def init_params(key, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """The whole tree at once (small sizes: the tests')."""
    ends = init_ends(key, sizes, dtype)
    return {"embed": ends["embed"], "lnf_g": ends["lnf_g"],
            "head": ends["head"],
            "blocks": [init_layer(layer_key(key, i), sizes,
                                  layer_kind(sizes, i), dtype)
                       for i in range(int(sizes["num_hidden_layers"]))]}


# -- the lower precisions of the controls --------------------------------------

def _fake_quant(x, qdtype=jnp.float8_e4m3fn):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(qdtype).max) / amax, 1.0)
    return (x * scale).astype(qdtype).astype(jnp.float32) / scale


def _bf16(x):
    """``x`` rounded to bfloat16's values (``reduce_precision``: the
    compiler may drop a cast to bfloat16 and back as excess precision,
    and on the chip it does)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _ops(precision: str):
    """(matmul, einsum, einsum with the recurrent state) of
    ``precision``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    if precision == "fp8":
        es = lambda eq, a, b: jnp.einsum(eq, _fake_quant(a), _fake_quant(b))
        return (lambda a, b: jnp.matmul(_fake_quant(a), _fake_quant(b)),
                es, es)
    if precision in STATED:
        # bfloat16 values multiply exactly in one pass, summed in float32
        bf = lambda a: a.astype(jnp.bfloat16)
        return (lambda a, b: jnp.matmul(
                    bf(a), bf(b), preferred_element_type=jnp.float32),
                lambda eq, a, b: jnp.einsum(
                    eq, bf(a), bf(b), preferred_element_type=jnp.float32),
                jnp.einsum)
    return jnp.matmul, jnp.einsum, jnp.einsum


def _kept(S, precision: str):
    """The recurrent state as ``precision`` keeps it from token to
    token."""
    return _bf16(S) if precision == "bf16_state" else S


def with_precision(precision: str):
    """Context under which the reference (or a control) multiplies: a
    control rounds operands or the state, the products stay exact."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return jax.default_matmul_precision("highest")


# -- the mathematics -----------------------------------------------------------------

def rms_norm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def gqa_mixer(p, x, sizes, precision="float32"):
    """Gated NoPE grouped-query attention over one sequence ``x`` [T, d],
    a block of query rows at a time."""
    mm, es, _ = _ops(precision)
    T = x.shape[0]
    H, KV = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    D = sizes["head_dim"]
    u = rms_norm(x, p["ln1_g"], sizes["rms_norm_eps"])
    q = mm(u, p["W_q"]).reshape(T, KV, H // KV, D)
    k = mm(u, p["W_k"]).reshape(T, KV, D)
    v = mm(u, p["W_v"]).reshape(T, KV, D)
    qb = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def block(args):
        q_b, first = args                                # [qb, KV, G, D]
        s = es("tgqd,lgd->gqtl", q_b, k) * D ** -0.5
        seen = jnp.arange(T)[None, :] <= (first + jnp.arange(qb))[:, None]
        a = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        return es("gqtl,lgd->tgqd", a, v).reshape(qb, H * D)

    att = jax.lax.map(block, (q.reshape(T // qb, qb, KV, H // KV, D),
                              jnp.arange(0, T, qb))).reshape(T, H * D)
    return mm(jax.nn.sigmoid(mm(u, p["W_g"])) * att, p["W_o"])


def linear_mixer(p, x, sizes, precision="float32", state_at=None):
    """The gated delta rule over one sequence ``x`` [T, d], token by
    token from a zero state.  Returns the mixer's output and the state
    ``S`` [heads, 128, 128] as row ``state_at`` left it (None: the last
    row): what a slot holds of the layer once that row is in."""
    mm, _, es = _ops(precision)
    la = sizes["linear_attn_config"]
    n, dl, taps = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    T, eps = x.shape[0], sizes["rms_norm_eps"]
    u = rms_norm(x, p["ln1_g"], eps)

    def conv_silu(a, w):
        """Causal depthwise convolution (zeros before the sequence), SiLU;
        at the stated precision of inputs held as the tail holds them."""
        if precision in STATED:
            a = _bf16(a)
        a = jnp.concatenate([jnp.zeros((taps - 1, a.shape[1])), a])
        return jax.nn.silu(sum(w[j] * a[j:j + T] for j in range(taps)))

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    c, w = n * dl, p["conv_w"]                 # taps of q | k | v
    q = unit(conv_silu(mm(u, p["W_q"]), w[:, :c]).reshape(T, n, dl)) \
        * dl ** -0.5
    k = unit(conv_silu(mm(u, p["W_k"]), w[:, c:2 * c]).reshape(T, n, dl))
    v = conv_silu(mm(u, p["W_v"]), w[:, 2 * c:]).reshape(T, n, dl)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        (mm(mm(u, p["W_f1"]), p["W_f2"]) + p["dt_bias"]).reshape(T, n, dl))
    beta = 2.0 * jax.nn.sigmoid(mm(u, p["W_b"]))

    def token(carry, row):
        S, held = carry
        t, q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[:, :, None] * S                  # Diag(alpha) S
        S = S + b_t[:, None, None] * k_t[:, :, None] * (
            v_t - es("hk,hkv->hv", k_t, S))[:, None, :]
        S = _kept(S, precision)
        return (S, jnp.where(t == last, S, held)), es("hk,hkv->hv", q_t, S)

    last = T - 1 if state_at is None else state_at
    zero = jnp.zeros((n, dl, dl), jnp.float32)
    (_, held), o = jax.lax.scan(token, (zero, zero),
                                (jnp.arange(T), q, k, v, g, beta))
    o = rms_norm(o, p["o_norm_g"], eps).reshape(T, c)
    return mm(o * jax.nn.sigmoid(mm(mm(u, p["W_g1"]), p["W_g2"])),
              p["W_o"]), held


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
    mm = _ops(precision)[0]
    first = sizes["first_expert"] if first_expert is None else first_expert
    idx, w = route(x, p["router_w"], p["router_b"],
                   sizes["num_experts_per_tok"],
                   sizes["routed_scaling_factor"])
    y = jnp.zeros_like(x)
    f32 = lambda a: a.astype(jnp.float32)   # an expert is widened when used
    for e in range(p["e_gate"].shape[0]):   # a loop over the experts held
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * gated_silu(x, f32(p["e_gate"][e]),
                                          f32(p["e_up"][e]),
                                          f32(p["e_down"][e]), mm)
    if with_shared and "s_gate" in p:
        y = y + gated_silu(x, p["s_gate"], p["s_up"], p["s_down"], mm)
    return y, jnp.sort(idx, axis=-1)


def layer(p, h, sizes, precision="float32", state_at=None):
    """One layer over one sequence ``h`` [T, d] float32, from weights in
    any type (widened here); its kind is read off its tree.  Returns
    ``(h, chosen ids [T, k], state)``: of a linear layer the recurrent
    state as row ``state_at`` left it (``linear_mixer``), of a
    grouped-query layer None."""
    p = {k: a if k.startswith("e_") else a.astype(jnp.float32)
         for k, a in p.items()}
    if "conv_w" in p:
        mixed, state = linear_mixer(p, h, sizes, precision, state_at)
    else:
        mixed, state = gqa_mixer(p, h, sizes, precision), None
    h = h + mixed
    y, picks = experts(p, rms_norm(h, p["ln2_g"], sizes["rms_norm_eps"]),
                       sizes, precision)
    return h + y, picks, state


def embed(ends, tokens):
    return ends["embed"].astype(jnp.float32)[tokens]


def logits(ends, h, sizes, precision="float32"):
    mm = _ops(precision)[0]
    return mm(rms_norm(h, ends["lnf_g"].astype(jnp.float32),
                       sizes["rms_norm_eps"]),
              ends["head"].astype(jnp.float32))


def forward(key, tokens, sizes, precision="float32", dtype=jnp.bfloat16):
    """Logits [T, V] and chosen ids [layers, T, k] of one sequence, layer
    by layer from the seed's key (small sizes: the tests' whole-model
    yardstick)."""
    ends = init_ends(key, sizes, dtype)
    h, picks = embed(ends, tokens), []
    for i in range(int(sizes["num_hidden_layers"])):
        p = init_layer(layer_key(key, i), sizes, layer_kind(sizes, i), dtype)
        h, pk, _ = layer(p, h, sizes, precision)
        picks.append(pk)
    return logits(ends, h, sizes, precision), picks
