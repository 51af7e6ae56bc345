"""Plain reference of configuration ``lfm2-24b-a2b``: one pipeline stage
of LiquidAI's LFM2-24B-A2B language model (``model_type: lfm2_moe``) in
``jax.numpy``.

float32 at ``highest`` matmul precision; no kernel, no cache, no
batching; imports nothing of the program.  The equations (``RMSNorm(x) =
g * x / sqrt(mean(x^2) + norm_eps)``, no bias anywhere; items marked
*assumed* are argued in the configuration's file under ``assumed``):

* ``h_0 = E[token]``; every layer ``a = x + Mix(RMSNorm_op(x))``, ``y = a
  + FF(RMSNorm_ffn(a))``; a final RMSNorm (the published
  ``embedding_norm``), then the TIED head: logits ``RMSNorm(h_L) E^T``
  (*assumed*: the catalog's config drops the key);
* ``Mix`` at a ``conv`` index of ``layer_types``, the gated short
  convolution: ``[B | C | X] = u W_in`` (three thirds of 6,144, *assumed*
  in that order); ``z = B * X``; ``c_t = sum_{j=0..2} w_j z_{t-2+j}`` a
  channel: causal, depthwise, ``conv_L_cache`` 3 taps, ``w_2`` on the
  current row, rows before the sequence zero, no bias, no activation;
  ``Mix = (C * c) W_out``.  It is computed as that three-term sum over
  the sequence with two zero rows put before it;
* ``Mix`` at a ``full_attention`` index: ``q, k, v = u W_q, u W_k, u W_v``
  as 32 / 8 / 8 heads of 64; ``q = RMSNorm_q(q)``, ``k = RMSNorm_k(k)``
  over each head's 64 values (one gain of 64 for all heads each); rotary
  over the whole head AFTER the norms (``rope_theta`` 1e6, default type,
  the halves ``(i, i + 32)`` paired); causal softmax at ``64^-0.5``,
  query head ``a`` reading KV head ``a // 4``; ``W_o``;
* ``FF`` of layer ``i < num_dense_layers``: ``(SiLU(v W_1) * (v W_3))
  W_2`` at width 11,776;
* ``FF`` elsewhere, routed: ``s = sigmoid(v W_r)`` (64 values, float32);
  the 4 experts with the largest ``s + b`` (``b`` the expert bias, a
  buffer, used for the choice only); weights ``s_e / (sum of the 4 chosen
  s + 1e-6)`` (*assumed* epsilon), times ``routed_scaling_factor`` 1; an
  expert is the gated SiLU form at width 1,536.  No shared expert, no
  capacity, no dropped pick.

The grouped-query attention is blocked over the query positions only so
that its scores fit.

The share: the router routes over all ``num_experts``; this chip holds
the ``n_routed_experts`` of the file from ``first_expert`` on (all 64
from 0 in the benchmark's file), and a pick on an expert that lives
elsewhere contributes nothing (a loop over the held experts, each
applied to the rows that picked it, weighted by the router's weight).

It is **computed in blocks**: ``init_layer`` makes one layer's weights
from the layer's own key, in the type the configuration states
(bfloat16: the very values the program holds); ``layer`` widens them and
applies the layer to one compared sequence; the caller frees them and
goes on.

Precisions.  ``float32`` is the reference.  The configuration states
bfloat16 weights, K/V rows and convolution tails with float32
accumulation, a float32 router and a float32 residual stream:
``bfloat16`` is the reference AT that stated precision, with nothing of
the program in it: both operands of every product but the router's
rounded to bfloat16 (the product itself exact, accumulated in float32),
the convolution's inputs rounded as the tail a slot keeps is.  The
nearest precisions below, the controls: ``fp8`` operands (e4m3, one scale
a tensor) in every product but the router's; and ``bf16_stream``, the
stated operands with the router's operands, logits and scores and the
residual stream after every addition rounded to bfloat16 where float32
is stated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: the sizes the functions here read from a configuration's file
SIZE_KEYS = (
    "vocab_size", "num_hidden_layers", "hidden_size", "num_attention_heads",
    "num_key_value_heads", "layer_types", "conv_L_cache",
    "intermediate_size", "moe_intermediate_size", "num_dense_layers",
    "num_experts", "n_routed_experts", "first_expert", "num_experts_per_tok",
    "routed_scaling_factor", "norm_eps", "rope_parameters",
    "initializer_range", "router_bias_std", "router_eps", "tie_embedding")
PRECISIONS = ("float32", "bfloat16", "fp8", "bf16_stream")
#: those whose operands are the stated bfloat16
STATED = ("bfloat16", "bf16_stream")
STATED_PRECISION = "bfloat16"
CONTROL_PRECISION = "fp8"
#: query rows whose scores are held at once
QUERY_BLOCK = 256
#: rows an expert is applied to at once, of those that chose it
EXPERT_ROWS = 128


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**32."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def layer_key(key, i: int):
    """Layer ``i``'s own key; -1 the embedding's, -2 an untied head's."""
    return jax.random.fold_in(key, i + 16)


def layer_kind(sizes: dict, i: int) -> str:
    """``"conv"`` or ``"full_attention"``, as ``layer_types`` names layer
    ``i`` (the published list, read up to the depth held)."""
    return sizes["layer_types"][i]


def is_dense(sizes: dict, i: int) -> bool:
    return i < int(sizes["num_dense_layers"])


def head_dim(sizes: dict) -> int:
    return sizes["hidden_size"] // sizes["num_attention_heads"]


# -- weights, one block at a time ----------------------------------------------

def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_mixer(key, sizes: dict, kind: str, dtype=jnp.bfloat16) -> dict:
    """The mixer's half of ``init_layer``'s tree, from the LAYER's key."""
    d, std = sizes["hidden_size"], float(sizes["initializer_range"])
    ks = jax.random.split(key, 16)
    N = functools.partial(_normal, std=std, dtype=dtype)
    p = {"ln1_g": jnp.ones((d,), dtype)}
    if kind == "full_attention":
        H, KV, D = (sizes["num_attention_heads"],
                    sizes["num_key_value_heads"], head_dim(sizes))
        gain = lambda k: jax.random.uniform(k, (D,), jnp.float32, 0.5,
                                            1.5).astype(dtype)
        p.update(W_q=N(ks[0], (d, H * D)), W_k=N(ks[1], (d, KV * D)),
                 W_v=N(ks[2], (d, KV * D)), W_o=N(ks[3], (H * D, d)),
                 q_norm_g=gain(ks[13]), k_norm_g=gain(ks[14]))
    else:
        taps = int(sizes["conv_L_cache"])
        p.update(W_in=N(ks[0], (d, 3 * d)), W_o=N(ks[3], (d, d)),
                 conv_w=jax.random.uniform(
                     ks[4], (taps, d), jnp.float32, -taps ** -0.5,
                     taps ** -0.5).astype(dtype))
    return p


def init_ff(key, sizes: dict, dense: bool, dtype=jnp.bfloat16) -> dict:
    """The feed-forward's half of ``init_layer``'s tree, from the LAYER's
    key (its draws are the mixer kind's business no more than the
    mixer's are its: a caller that makes the halves apart makes an
    expert layer's 604 M values by one program whatever the mixer)."""
    d, std = sizes["hidden_size"], float(sizes["initializer_range"])
    ks = jax.random.split(key, 16)
    N = functools.partial(_normal, std=std, dtype=dtype)
    p = {"ln2_g": jnp.ones((d,), dtype)}
    if dense:
        f = sizes["intermediate_size"]
        p.update(W_gate=N(ks[5], (d, f)), W_up=N(ks[6], (d, f)),
                 W_down=N(ks[7], (f, d)))
        return p
    f, held, E = (sizes["moe_intermediate_size"], sizes["n_routed_experts"],
                  sizes["num_experts"])
    p.update(router_w=N(ks[8], (d, E)),
             router_b=float(sizes["router_bias_std"]) * jax.random.normal(
                 ks[9], (E,), jnp.float32),
             e_gate=N(ks[10], (held, d, f)), e_up=N(ks[11], (held, d, f)),
             e_down=N(ks[12], (held, f, d)))
    return p


def init_layer(key, sizes: dict, kind: str, dense: bool,
               dtype=jnp.bfloat16) -> dict:
    """One layer's weights from ITS key: N(0, initializer_range) matrices,
    unit gains on the stream's norms; of a grouped-query layer the two
    gains of the q/k norm U(0.5, 1.5) a channel (with gains all one the
    norm and the rotary after it commute, and their order would be held
    by nothing); of a convolution layer the taps U(-3^-0.5, 3^-0.5) (a
    depthwise Conv1d's default at 3 taps); of an expert layer the router's
    bias N(0, router_bias_std) in float32 (non-zero, so the choice-only
    path is worked; the configuration's file says why it is small)."""
    return {**init_mixer(key, sizes, kind, dtype),
            **init_ff(key, sizes, dense, dtype)}


def init_ends(key, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """The embedding, which is the head too, and the final norm's gain; an
    untied head where the file says ``tie_embedding`` false."""
    d, V = sizes["hidden_size"], sizes["vocab_size"]
    std = float(sizes["initializer_range"])
    ends = {"embed": _normal(layer_key(key, -1), (V, d), std, dtype),
            "lnf_g": jnp.ones((d,), dtype)}
    if not sizes["tie_embedding"]:
        ends["head"] = _normal(layer_key(key, -2), (d, V), std, dtype)
    return ends


def init_params(key, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """The whole tree at once (small sizes: the tests')."""
    return {**init_ends(key, sizes, dtype),
            "blocks": [init_layer(layer_key(key, i), sizes,
                                  layer_kind(sizes, i), is_dense(sizes, i),
                                  dtype)
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
    """(matmul, einsum) of ``precision``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    if precision == "fp8":
        return (lambda a, b: jnp.matmul(_fake_quant(a), _fake_quant(b)),
                lambda eq, a, b: jnp.einsum(eq, _fake_quant(a),
                                            _fake_quant(b)))
    if precision in STATED:
        # bfloat16 values multiply exactly in one pass, summed in float32
        bf = lambda a: a.astype(jnp.bfloat16)
        return (lambda a, b: jnp.matmul(
                    bf(a), bf(b), preferred_element_type=jnp.float32),
                lambda eq, a, b: jnp.einsum(
                    eq, bf(a), bf(b), preferred_element_type=jnp.float32))
    return jnp.matmul, jnp.einsum


def _stream(h, precision: str):
    """The residual stream as ``precision`` keeps it after an addition."""
    return _bf16(h) if precision == "bf16_stream" else h


def with_precision(precision: str):
    """Context under which the reference (or a control) multiplies: a
    control rounds operands, the router or the stream, the products stay
    exact."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return jax.default_matmul_precision("highest")


# -- the mathematics -----------------------------------------------------------------

def rms_norm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def rotary(x, theta: float):
    """Rotary over the whole last axis of ``x`` [T, heads, D] at positions
    0 .. T - 1: frequency ``i`` is ``theta^(-2i / D)``, the halves ``(i, i
    + D / 2)`` paired."""
    T, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + rot * sin


def attention_mixer(p, x, sizes, precision="float32"):
    """Grouped-query attention with q/k norm and rotary over one sequence
    ``x`` [T, d], a block of query rows at a time."""
    mm, es = _ops(precision)
    T, eps = x.shape[0], sizes["norm_eps"]
    H, KV = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    D = head_dim(sizes)
    theta = float(sizes["rope_parameters"]["rope_theta"])
    u = rms_norm(x, p["ln1_g"], eps)
    q = rms_norm(mm(u, p["W_q"]).reshape(T, H, D), p["q_norm_g"], eps)
    k = rms_norm(mm(u, p["W_k"]).reshape(T, KV, D), p["k_norm_g"], eps)
    q = rotary(q, theta).reshape(T, KV, H // KV, D)
    k = rotary(k, theta)
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
    return mm(att, p["W_o"])


def conv_mixer(p, x, sizes, precision="float32", state_at=None):
    """The gated short convolution over one sequence ``x`` [T, d] from
    zero rows.  Returns the mixer's output and the tail [taps - 1, d] as
    row ``state_at`` left it (None: the last row): the last ``taps - 1``
    rows of ``z`` up to that row, what a slot holds of the layer once the
    row is in."""
    mm, _ = _ops(precision)
    d, taps, T = sizes["hidden_size"], int(sizes["conv_L_cache"]), x.shape[0]
    bcx = mm(rms_norm(x, p["ln1_g"], sizes["norm_eps"]), p["W_in"])
    B, C, X = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = B * X
    if precision in STATED:     # inputs held as the tail a slot keeps holds them
        z = _bf16(z)
    # rows before the sequence are zero; the last tap meets the row itself
    z = jnp.concatenate([jnp.zeros((taps - 1, d)), z])
    c = sum(p["conv_w"][j] * z[j:j + T] for j in range(taps))
    last = T - 1 if state_at is None else state_at
    tail = jax.lax.dynamic_slice_in_dim(z, last + 1, taps - 1, axis=0)
    return mm(C * c, p["W_o"]), tail


def gated_silu(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def route(x, router_w, router_b, k: int, scaling: float, eps: float,
          precision: str = "float32"):
    """(ids [T, k], weights [T, k]) of the gate: sigmoid scores, the ``k``
    largest of ``scores + bias``, weights the chosen SCORES over their sum
    ``+ eps``, times ``scaling``; float32 in every precision (the
    configuration states a float32 router) but ``bf16_stream``, whose
    operands, logits and scores are rounded to bfloat16."""
    if precision == "bf16_stream":
        logits = _bf16(jnp.matmul(_bf16(x), _bf16(router_w)))
        s = _bf16(jax.nn.sigmoid(logits))
    else:
        s = jax.nn.sigmoid(jnp.matmul(x, router_w))
    _, idx = jax.lax.top_k(s + router_b, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / (jnp.sum(w, axis=-1, keepdims=True) + eps) * scaling


def experts(p, x, sizes, precision="float32", first_expert=None):
    """The held experts' part of the layer for ``x`` [T, d] (experts
    ``first_expert ..`` of the router's range live in ``p``); and the
    chosen ids [T, k], ascending.  A loop over the experts held; each is
    applied to the rows that chose it and to no others, ``EXPERT_ROWS``
    at a time (a row that did not choose it would be weighted 0: one
    sequence of the benchmark is up to 3,072 rows, of which an expert
    sees a sixteenth)."""
    mm = _ops(precision)[0]
    first = sizes["first_expert"] if first_expert is None else first_expert
    idx, w = route(x, p["router_w"], p["router_b"],
                   sizes["num_experts_per_tok"],
                   float(sizes["routed_scaling_factor"]),
                   float(sizes["router_eps"]), precision)
    T, held = x.shape[0], p["e_gate"].shape[0]
    rows = min(EXPERT_ROWS, T)
    pad = -T % rows
    f32 = lambda a: a.astype(jnp.float32)   # an expert is widened when used

    def one(y, e):
        chose = idx == first + e
        w_e = jnp.sum(jnp.where(chose, w, 0.0), axis=-1)
        mine = jnp.any(chose, axis=-1)
        n = jnp.sum(mine)
        # the rows that chose ``e`` first, in their order; row 0 fills up
        order = jnp.pad(jnp.argsort(~mine, stable=True), (0, pad))
        w_gate, w_up, w_down = (f32(p[name][e])
                                for name in ("e_gate", "e_up", "e_down"))

        def block(i, y):
            at = jax.lax.dynamic_slice_in_dim(order, i * rows, rows)
            real = i * rows + jnp.arange(rows) < n
            out = gated_silu(x[at], w_gate, w_up, w_down, mm)
            return y.at[at].add(jnp.where(real, w_e[at], 0.0)[:, None] * out)

        return jax.lax.fori_loop(0, (n + rows - 1) // rows, block, y), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    return y, jnp.sort(idx, axis=-1)


def layer(p, h, sizes, precision="float32", state_at=None):
    """One layer over one sequence ``h`` [T, d] float32, from weights in
    any type (widened here); its kinds are read off its tree.  Returns
    ``(h, chosen ids [T, k] or None of a dense layer, tail or None)``: of
    a convolution layer the tail as row ``state_at`` left it
    (``conv_mixer``), of a grouped-query layer None."""
    p = {k: a if k.startswith("e_") else a.astype(jnp.float32)
         for k, a in p.items()}
    if "W_in" in p:
        mixed, tail = conv_mixer(p, h, sizes, precision, state_at)
    else:
        mixed, tail = attention_mixer(p, h, sizes, precision), None
    h = _stream(h + mixed, precision)
    v = rms_norm(h, p["ln2_g"], sizes["norm_eps"])
    if "W_gate" in p:
        y, picks = gated_silu(v, p["W_gate"], p["W_up"], p["W_down"],
                              _ops(precision)[0]), None
    else:
        y, picks = experts(p, v, sizes, precision)
    return _stream(h + y, precision), picks, tail


def embed(ends, tokens):
    return ends["embed"].astype(jnp.float32)[tokens]


def logits(ends, h, sizes, precision="float32"):
    mm = _ops(precision)[0]
    head = ends["embed"].astype(jnp.float32).T if sizes["tie_embedding"] \
        else ends["head"].astype(jnp.float32)
    return mm(rms_norm(h, ends["lnf_g"].astype(jnp.float32),
                       sizes["norm_eps"]), head)


def forward(key, tokens, sizes, precision="float32", dtype=jnp.bfloat16):
    """Logits [T, V] and chosen ids [expert layers, T, k] of one sequence,
    layer by layer from the seed's key (small sizes: the tests'
    whole-model yardstick)."""
    ends = init_ends(key, sizes, dtype)
    h, picks = _stream(embed(ends, tokens), precision), []
    for i in range(int(sizes["num_hidden_layers"])):
        p = init_layer(layer_key(key, i), sizes, layer_kind(sizes, i),
                       is_dense(sizes, i), dtype)
        h, pk, _ = layer(p, h, sizes, precision)
        if pk is not None:
            picks.append(pk)
    return logits(ends, h, sizes, precision), picks
