"""Plain GPT-2 in ``jax.numpy``: the yardstick both GPT-2 configurations
are compared with.

Pre-LN decoder blocks, learned positions, biased tanh-GELU FFN, causal
softmax attention, mean token cross-entropy, plain Adam.  No kernels, no
cache, no batching tricks; float32 with ``highest`` matmul precision
unless a lower ``precision`` is asked for (the control).  Imports nothing
of the program under test.  The parameter tree is the benchmark's own:
the runners hand the same tree (made here from the seed) to the program.

Departures from the published model, both forced by the program's
``ShardedTransformerLM`` and listed in the configuration files: the
output head is a matrix of its own (GPT-2 ties it to the embedding), and
the query/key/value projections carry no bias.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCK_KEYS = ("ln1_g", "ln1_b", "Wq", "Wk", "Wv", "Wo", "bo",
              "ln2_g", "ln2_b", "W1", "b1", "W2", "b2")
#: the sizes ``init_params`` reads from a configuration's file
SIZE_KEYS = ("vocab_size", "n_layer", "n_embd", "n_head", "n_inner",
             "n_positions", "initializer_range")
LN_EPS = 1e-5          # layer_norm_epsilon of the published config.json
PRECISIONS = ("float32", "bfloat16", "fp8")


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**32."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def init_params(key, sizes: dict, dtype=jnp.float32) -> dict:
    """GPT-2's own initialisation from ``key`` (``seed_key(seed)``):
    N(0, 0.02) matrices (the two residual projections scaled by
    1/sqrt(2 layers)), zero biases, unit layer-norm gains.  One traceable
    function of the key, so a runner makes all weights on the device in
    one jitted call that is compiled once for every seed."""
    v, n, d = sizes["vocab_size"], sizes["n_layer"], sizes["n_embd"]
    f, t = sizes["n_inner"], sizes["n_positions"]
    std = float(sizes["initializer_range"])
    ke, kp, kh, kq, kk, kv, ko, k1, k2 = jax.random.split(key, 9)
    res = std / (2.0 * n) ** 0.5

    def normal(key, shape, std=std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    ones = lambda *s: jnp.ones(s, dtype)
    zeros = lambda *s: jnp.zeros(s, dtype)
    blocks = {
        "ln1_g": ones(n, d), "ln1_b": zeros(n, d),
        "Wq": normal(kq, (n, d, d)), "Wk": normal(kk, (n, d, d)),
        "Wv": normal(kv, (n, d, d)), "Wo": normal(ko, (n, d, d), res),
        "bo": zeros(n, d),
        "ln2_g": ones(n, d), "ln2_b": zeros(n, d),
        "W1": normal(k1, (n, d, f)), "b1": zeros(n, f),
        "W2": normal(k2, (n, f, d), res), "b2": zeros(n, d),
    }
    return {"embed": normal(ke, (v, d)), "pos": normal(kp, (t, d)),
            "blocks": blocks, "lnf_g": ones(d), "lnf_b": zeros(d),
            "head": normal(kh, (d, v))}


# -- the lower precisions of the control ------------------------------------

def _fake_quant(x, qdtype):
    """Round ``x`` through an 8-bit float type with one scale per tensor
    (amax mapped to the type's largest finite value)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(qdtype).max) / amax, 1.0)
    return (x * scale).astype(qdtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8_matmul(x, w):
    q = functools.partial(_fake_quant, qdtype=jnp.float8_e4m3fn)
    return jnp.matmul(q(x), q(w))


def _fp8_fwd(x, w):
    return _fp8_matmul(x, w), (x, w)


def _fp8_bwd(res, g):
    x, w = res
    q = functools.partial(_fake_quant, qdtype=jnp.float8_e4m3fn)
    gq = _fake_quant(g, jnp.float8_e5m2)
    dx = jnp.matmul(gq, jnp.swapaxes(q(w), -1, -2))
    x2 = q(x).reshape(-1, x.shape[-1])
    dw = jnp.matmul(x2.T, gq.reshape(-1, g.shape[-1]))
    return dx, dw


_fp8_matmul.defvjp(_fp8_fwd, _fp8_bwd)


def _matmul_for(precision: str):
    if precision == "fp8":
        return _fp8_matmul
    return jnp.matmul


# -- the model ----------------------------------------------------------------

def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu_tanh(x):
    c = (2.0 / jnp.pi) ** 0.5
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def _block(p, h, n_heads, mm):
    b, t, d = h.shape
    dh = d // n_heads
    u = _layer_norm(h, p["ln1_g"], p["ln1_b"])
    heads = lambda y: y.reshape(b, t, n_heads, dh).transpose(0, 2, 1, 3)
    q, k, v = heads(mm(u, p["Wq"])), heads(mm(u, p["Wk"])), heads(mm(u, p["Wv"]))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (dh ** 0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, jnp.asarray(-1e30, s.dtype))
    a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(h.dtype)
    o = jnp.einsum("bhqk,bhkd->bhqd", a, v)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, d)
    h = h + mm(o, p["Wo"]) + p["bo"]
    u = _layer_norm(h, p["ln2_g"], p["ln2_b"])
    return h + mm(_gelu_tanh(mm(u, p["W1"]) + p["b1"]), p["W2"]) + p["b2"]


def hidden(params, tokens, n_heads, precision="float32"):
    """Final-layer-norm hidden states [B, T, d] of ``tokens`` [B, T]."""
    mm = _matmul_for(precision)
    h = params["embed"][tokens] + params["pos"][: tokens.shape[1]]
    block = jax.checkpoint(functools.partial(_block, n_heads=n_heads, mm=mm))
    h, _ = jax.lax.scan(lambda h, p: (block(p, h), None), h,
                        {k: params["blocks"][k] for k in BLOCK_KEYS})
    return _layer_norm(h, params["lnf_g"], params["lnf_b"])


def with_precision(precision: str):
    """Context under which the reference (or its control) multiplies."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return jax.default_matmul_precision(
        "default" if precision == "bfloat16" else "highest")


def cast_params(params, precision: str):
    """The parameter tree in the type ``precision`` computes in: bfloat16
    for the bfloat16 control, float32 otherwise (the fp8 control rounds
    the operands of each product, not the stored weights)."""
    dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    return jax.tree_util.tree_map(lambda a: a.astype(dt), params)


def logits(params, tokens, n_heads, precision="float32"):
    mm = _matmul_for(precision)
    return mm(hidden(params, tokens, n_heads, precision), params["head"])


def loss_sum(params, tokens, targets, n_heads, precision="float32"):
    """Summed token cross-entropy (float32) of one block of rows."""
    lg = logits(params, tokens, n_heads, precision).astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


def adam_step(params, grads, m, v, it, lr, beta1=0.9, beta2=0.999, eps=1e-8):
    """Plain float32 Adam, step ``it`` counted from 0."""
    t = it + 1.0
    tm = jax.tree_util.tree_map
    m = tm(lambda a, g: beta1 * a + (1 - beta1) * g, m, grads)
    v = tm(lambda a, g: beta2 * a + (1 - beta2) * g * g, v, grads)
    bc1, bc2 = 1.0 - beta1 ** t, 1.0 - beta2 ** t
    params = tm(lambda p, a, b: p - lr * (a / bc1) / (jnp.sqrt(b / bc2) + eps),
                params, m, v)
    return params, m, v
