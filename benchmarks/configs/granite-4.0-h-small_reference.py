"""Plain reference of configuration ``granite-4.0-h-small``: the chip's
share of IBM Granite 4.0-H Small's language model (``model_type:
granitemoehybrid``) in ``jax.numpy``.

float32 at ``highest`` matmul precision; no kernel, no cache, no chunked
form, no batching; imports nothing of the program.  The equations
(``u = RMSNorm(x)`` with gain, eps ``rms_norm_eps``; ``r`` is
``residual_multiplier``; items marked *assumed* are argued in the
configuration's file under ``assumed``):

* ``h_0 = embedding_multiplier * E[token]``; every layer ``h = x + r
  Mix(u)``, ``y = h + r (Experts(v) + Shared(v))``, ``v = RMSNorm(h)``; a
  final RMSNorm, then the TIED head: logits ``RMSNorm(h_L) E^T /
  logits_scaling``, ``E`` the held slice of the embedding;
* ``Experts``: router logits over all 72 in float32, the 10 largest,
  weights ``softmax`` over those 10; an expert is ``(SiLU(v W_a) * (v
  W_b)) W_c`` at width 768 (``input_linear``'s two halves, the first
  under the SiLU), without biases; ``Shared`` the same at width 1536;
* ``Mix`` at an ``attention`` index of ``layer_types``: ``q = u W_q`` (32
  heads of 128), ``k = u W_k``, ``v = u W_v`` (8 heads of 128; query head
  ``a`` reads KV head ``a // 4``), NO position of any kind
  (``position_embedding_type`` nope), no gate, no bias, causal softmax
  at scale ``attention_multiplier`` = 1/128 over every earlier row,
  ``W_o``;
* ``Mix`` elsewhere (Mamba-2, one group): ``[z | xBC | dt] = u W_in``
  (8192 | 8448 | 128, no bias); ``xBC = SiLU(conv4(xBC) + b)``, a causal
  depthwise convolution of 4 taps a channel WITH bias; ``[x | B | C]`` =
  8192 | 128 | 128, ``x`` as 128 heads of 64; ``dt_t,h = softplus(dt_t,h
  + dt_bias_h)`` (the published clamp is (0, inf): nothing); ``A_h =
  -exp(A_log_h)``; a state ``S`` [64 x 128] a head:

      S_t,h = exp(dt_t,h A_h) S_t-1,h + dt_t,h x_t,h B_t^T
      o_t,h = S_t,h C_t + D_h x_t,h

  then ``out = W_o RMSNorm(o * SiLU(z))``, the norm over all 8192
  channels at once (one group) with its gain, the gate BEFORE the norm.
  *Assumed*: ``A_log = log U(1, 16)`` a head, ``softplus(dt_bias)``
  log-uniform in [0.001, 0.1], ``D = 1``, the taps and their bias U(-0.5,
  0.5), gains one, every matrix N(0, ``initializer_range``).

The state-space layers run **token by token** by the recurrence as
written (a ``lax.scan`` over the positions whose carry is ``S``).  The
grouped-query attention is blocked over the query positions only so that
its scores fit.

The share: the router routes over all 72 experts; this chip holds the
``num_local_experts`` of the file from ``first_expert`` on, and a pick
on an expert that lives elsewhere contributes nothing (a loop over the
held experts, each weighted by the router's weight where it was picked
and 0 elsewhere).  The vocabulary is the file's slice.

It is **computed in blocks**: ``init_layer`` makes one layer's weights
from the layer's own key, in the type the configuration states
(bfloat16: the very values the program holds); ``layer`` widens them and
applies the layer to one compared sequence; the caller frees them and
goes on.

Precisions.  ``float32`` is the reference.  The configuration states
bfloat16 weights, K/V rows and convolution tails with float32
accumulation, a float32 router and a float32 state: ``bfloat16`` is the
reference AT that stated precision, with nothing of the program in it:
both operands of every product but the router's and the state's rounded
to bfloat16 (the product itself exact, accumulated in float32), the
convolution's inputs rounded as the tail a slot keeps is, the state and
every product with it float32.  The nearest precisions below, the
controls: ``fp8`` operands (e4m3, one scale a tensor) in every product
but the router's; and ``bf16_state``, the stated precision with the
state rounded to bfloat16 after every token where float32 is stated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: the sizes the functions here read from a configuration's file
SIZE_KEYS = (
    "vocab_size", "num_hidden_layers", "hidden_size", "num_attention_heads",
    "num_key_value_heads", "layer_types", "mamba_n_heads", "mamba_d_head",
    "mamba_d_state", "mamba_d_conv", "mamba_conv_bias", "intermediate_size",
    "shared_intermediate_size", "num_local_experts",
    "num_local_experts_published", "first_expert", "num_experts_per_tok",
    "rms_norm_eps", "initializer_range", "embedding_multiplier",
    "residual_multiplier", "logits_scaling", "attention_multiplier")
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
    """Layer ``i``'s own key; -1 the embedding's."""
    return jax.random.fold_in(key, i + 16)


def layer_kind(sizes: dict, i: int) -> str:
    """``"attention"`` or ``"mamba"``, as ``layer_types`` names layer
    ``i`` (the published list, read up to the depth held)."""
    return sizes["layer_types"][i]


def head_dim(sizes: dict) -> int:
    return sizes["hidden_size"] // sizes["num_attention_heads"]


# -- weights, one block at a time ----------------------------------------------

def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_layer(key, sizes: dict, kind: str, dtype=jnp.bfloat16) -> dict:
    """One layer's weights from ITS key: N(0, initializer_range) matrices,
    unit gains; of a state-space layer also the taps and their bias
    U(-0.5, 0.5), ``A_log``, ``D`` and ``dt_bias`` (float32) as the
    docstring draws them."""
    d, std = sizes["hidden_size"], float(sizes["initializer_range"])
    ks = jax.random.split(key, 24)
    N = functools.partial(_normal, std=std, dtype=dtype)
    p = {"ln1_g": jnp.ones((d,), dtype), "ln2_g": jnp.ones((d,), dtype)}
    if kind == "attention":
        H, KV, D = (sizes["num_attention_heads"],
                    sizes["num_key_value_heads"], head_dim(sizes))
        p.update(W_q=N(ks[0], (d, H * D)), W_k=N(ks[1], (d, KV * D)),
                 W_v=N(ks[2], (d, KV * D)), W_o=N(ks[3], (H * D, d)))
    else:
        nh, taps = sizes["mamba_n_heads"], sizes["mamba_d_conv"]
        di = nh * sizes["mamba_d_head"]
        cw = di + 2 * sizes["mamba_d_state"]
        dt = jnp.exp(jax.random.uniform(ks[6], (nh,), jnp.float32,
                                        jnp.log(0.001), jnp.log(0.1)))
        p.update(
            W_in=N(ks[0], (d, di + cw + nh)), W_o=N(ks[3], (di, d)),
            conv_w=jax.random.uniform(ks[4], (taps, cw), jnp.float32,
                                      -0.5, 0.5).astype(dtype),
            A_log=jnp.log(jax.random.uniform(ks[7], (nh,), jnp.float32,
                                             1.0, 16.0)),
            D=jnp.ones((nh,), jnp.float32),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1(dt)
            norm_g=jnp.ones((di,), dtype))
        if sizes["mamba_conv_bias"]:
            p["conv_b"] = jax.random.uniform(
                ks[5], (cw,), jnp.float32, -0.5, 0.5).astype(dtype)
    f, held = sizes["intermediate_size"], sizes["num_local_experts"]
    p.update(router_w=N(ks[13], (d, sizes["num_local_experts_published"])),
             e_gate=N(ks[15], (held, d, f)), e_up=N(ks[16], (held, d, f)),
             e_down=N(ks[17], (held, f, d)))
    fs = sizes["shared_intermediate_size"]
    if fs:
        p.update(s_gate=N(ks[18], (d, fs)), s_up=N(ks[19], (d, fs)),
                 s_down=N(ks[20], (fs, d)))
    return p


def init_ends(key, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """The embedding, which is the head too, and the final norm's gain."""
    d, V = sizes["hidden_size"], sizes["vocab_size"]
    std = float(sizes["initializer_range"])
    return {"embed": _normal(layer_key(key, -1), (V, d), std, dtype),
            "lnf_g": jnp.ones((d,), dtype)}


def init_params(key, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """The whole tree at once (small sizes: the tests')."""
    return {**init_ends(key, sizes, dtype),
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
    """(matmul, einsum, product with the state: of two operands that
    broadcast) of ``precision``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    if precision == "fp8":
        return (lambda a, b: jnp.matmul(_fake_quant(a), _fake_quant(b)),
                lambda eq, a, b: jnp.einsum(eq, _fake_quant(a),
                                            _fake_quant(b)),
                lambda a, b: _fake_quant(a) * _fake_quant(b))
    if precision in STATED:
        # bfloat16 values multiply exactly in one pass, summed in float32
        bf = lambda a: a.astype(jnp.bfloat16)
        return (lambda a, b: jnp.matmul(
                    bf(a), bf(b), preferred_element_type=jnp.float32),
                lambda eq, a, b: jnp.einsum(
                    eq, bf(a), bf(b), preferred_element_type=jnp.float32),
                jnp.multiply)
    return jnp.matmul, jnp.einsum, jnp.multiply


def _kept(S, precision: str):
    """The state as ``precision`` keeps it from token to token."""
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


def attention_mixer(p, x, sizes, precision="float32"):
    """Ungated NoPE grouped-query attention over one sequence ``x`` [T,
    d], a block of query rows at a time."""
    mm, es, _ = _ops(precision)
    T = x.shape[0]
    H, KV = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    D = head_dim(sizes)
    u = rms_norm(x, p["ln1_g"], sizes["rms_norm_eps"])
    q = mm(u, p["W_q"]).reshape(T, KV, H // KV, D)
    k = mm(u, p["W_k"]).reshape(T, KV, D)
    v = mm(u, p["W_v"]).reshape(T, KV, D)
    qb = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def block(args):
        q_b, first = args                                # [qb, KV, G, D]
        s = es("tgqd,lgd->gqtl", q_b, k) * sizes["attention_multiplier"]
        seen = jnp.arange(T)[None, :] <= (first + jnp.arange(qb))[:, None]
        a = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        return es("gqtl,lgd->tgqd", a, v).reshape(qb, H * D)

    att = jax.lax.map(block, (q.reshape(T // qb, qb, KV, H // KV, D),
                              jnp.arange(0, T, qb))).reshape(T, H * D)
    return mm(att, p["W_o"])


def mamba_mixer(p, x, sizes, precision="float32", state_at=None):
    """Mamba-2 over one sequence ``x`` [T, d], token by token from a zero
    state.  Returns the mixer's output and the state ``S`` [heads, 64,
    128] as row ``state_at`` left it (None: the last row): what a slot
    holds of the layer once that row is in."""
    mm, _, sp = _ops(precision)
    nh, dh, ds = (sizes["mamba_n_heads"], sizes["mamba_d_head"],
                  sizes["mamba_d_state"])
    taps, di = sizes["mamba_d_conv"], nh * dh
    T, eps = x.shape[0], sizes["rms_norm_eps"]
    zxd = mm(rms_norm(x, p["ln1_g"], eps), p["W_in"])
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + di + 2 * ds], zxd[:, -nh:]
    if precision in STATED:     # inputs held as the tail a slot keeps holds them
        xbc = _bf16(xbc)
    # causal depthwise convolution (zeros before the sequence), bias, SiLU
    xbc = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    xbc = sum(p["conv_w"][j] * xbc[j:j + T] for j in range(taps))
    xbc = jax.nn.silu(xbc + p["conv_b"] if "conv_b" in p else xbc)
    xs = xbc[:, :di].reshape(T, nh, dh)
    B, C = xbc[:, di:di + ds], xbc[:, di + ds:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    def token(carry, row):
        S, held = carry
        t, x_t, B_t, C_t, dt_t = row
        S = sp(jnp.exp(dt_t * A)[:, None, None], S) \
            + sp((dt_t[:, None] * x_t)[:, :, None], B_t[None, None, :])
        S = _kept(S, precision)
        o = jnp.sum(sp(S, C_t[None, None, :]), axis=-1) + p["D"][:, None] * x_t
        return (S, jnp.where(t == last, S, held)), o

    last = T - 1 if state_at is None else state_at
    zero = jnp.zeros((nh, dh, ds), jnp.float32)
    (_, held), o = jax.lax.scan(token, (zero, zero),
                                (jnp.arange(T), xs, B, C, dt))
    g = o.reshape(T, di) * jax.nn.silu(z)        # the gate BEFORE the norm
    return mm(rms_norm(g, p["norm_g"], eps), p["W_o"]), held


def gated_silu(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def route(x, router_w, k: int):
    """(ids [T, k], weights [T, k]): the ``k`` largest of the router's
    logits, softmax over those ``k``; float32 in every precision (the
    configuration states a float32 router)."""
    top, idx = jax.lax.top_k(jnp.matmul(x, router_w), k)
    return idx, jax.nn.softmax(top, axis=-1)


def experts(p, x, sizes, precision="float32", first_expert=None,
            with_shared=True):
    """The held experts' part of the layer for ``x`` [T, d] (experts
    ``first_expert ..`` of the router's range live in ``p``), plus the
    shared MLP; and the chosen ids [T, k], ascending."""
    mm = _ops(precision)[0]
    first = sizes["first_expert"] if first_expert is None else first_expert
    idx, w = route(x, p["router_w"], sizes["num_experts_per_tok"])
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
    ``(h, chosen ids [T, k], state)``: of a state-space layer the state
    as row ``state_at`` left it (``mamba_mixer``), of a grouped-query
    layer None."""
    p = {k: a if k.startswith("e_") else a.astype(jnp.float32)
         for k, a in p.items()}
    r = sizes["residual_multiplier"]
    if "W_in" in p:
        mixed, state = mamba_mixer(p, h, sizes, precision, state_at)
    else:
        mixed, state = attention_mixer(p, h, sizes, precision), None
    h = h + r * mixed
    y, picks = experts(p, rms_norm(h, p["ln2_g"], sizes["rms_norm_eps"]),
                       sizes, precision)
    return h + r * y, picks, state


def embed(ends, tokens, sizes):
    return sizes["embedding_multiplier"] \
        * ends["embed"].astype(jnp.float32)[tokens]


def logits(ends, h, sizes, precision="float32"):
    mm = _ops(precision)[0]
    return mm(rms_norm(h, ends["lnf_g"].astype(jnp.float32),
                       sizes["rms_norm_eps"]),
              ends["embed"].astype(jnp.float32).T) / sizes["logits_scaling"]


def forward(key, tokens, sizes, precision="float32", dtype=jnp.bfloat16):
    """Logits [T, V] and chosen ids [layers, T, k] of one sequence, layer
    by layer from the seed's key (small sizes: the tests' whole-model
    yardstick)."""
    ends = init_ends(key, sizes, dtype)
    h, picks = embed(ends, tokens, sizes), []
    for i in range(int(sizes["num_hidden_layers"])):
        p = init_layer(layer_key(key, i), sizes, layer_kind(sizes, i), dtype)
        h, pk, _ = layer(p, h, sizes, precision)
        picks.append(pk)
    return logits(ends, h, sizes, precision), picks
