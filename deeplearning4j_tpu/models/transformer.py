"""TransformerLM — the long-context flagship (no reference analog).

DL4J 0.9.2's sequence flagship is TextGenerationLSTM
(zoo/model/TextGenerationLSTM.java); the TPU framework adds a decoder-only
transformer LM as the model that exercises every modern axis the SURVEY
mandates (§2.3/§5): flash attention (pallas), ring attention over ``seq``,
tensor-parallel FFN/heads over ``model``, and a GPipe pipeline over
``pipe`` (parallel/transformer.py drives the 4D-parallel train step).

``block_params``/``block_apply`` are the single source of truth for the
block math — the TransformerBlock layer (single-chip MLN path) and the
ShardedTransformerLM (multi-chip path) both call them, so parity between
the two is structural rather than tested-for.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..nn.conf.inputs import InputType
from ..nn.layers import EmbeddingSequence, RnnOutputLayer
from ..nn.layers.base import Array, ForwardOut, Layer, register_layer
from ..nn.layers.normalization import layer_norm
from ..nn.multilayer import MultiLayerNetwork, NeuralNetConfiguration
from ..nn.updaters import Adam, GradientNormalization
from ..ops.attention import flash_mha, merge_heads, mha, split_heads
from ..ops.initializers import init_weight


def block_params(rng: Array, d_model: int, n_heads: int, d_ff: int,
                 dtype=jnp.float32, weight_init: str = "xavier") -> Dict[str, Array]:
    """One pre-LN transformer block's parameter tree."""
    kq, kk, kv, ko, k1, k2 = jax.random.split(rng, 6)
    d = d_model
    return {
        "ln1_g": jnp.ones((d,), dtype), "ln1_b": jnp.zeros((d,), dtype),
        "Wq": init_weight(kq, (d, d), weight_init, d, d, dtype),
        "Wk": init_weight(kk, (d, d), weight_init, d, d, dtype),
        "Wv": init_weight(kv, (d, d), weight_init, d, d, dtype),
        "Wo": init_weight(ko, (d, d), weight_init, d, d, dtype),
        "bo": jnp.zeros((d,), dtype),
        "ln2_g": jnp.ones((d,), dtype), "ln2_b": jnp.zeros((d,), dtype),
        "W1": init_weight(k1, (d, d_ff), weight_init, d, d_ff, dtype),
        "b1": jnp.zeros((d_ff,), dtype),
        "W2": init_weight(k2, (d_ff, d), weight_init, d_ff, d, dtype),
        "b2": jnp.zeros((d,), dtype),
    }


def block_apply(p: Dict[str, Array], h: Array, n_heads: int, *,
                causal: bool = True,
                attention_fn: Optional[Callable] = None,
                psum_axis: Optional[str] = None) -> Array:
    """Pre-LN block: h + attn(LN(h)); h + FFN(LN(h)).

    ``attention_fn(q, k, v)`` defaults to the pallas flash kernel; the
    sharded trainer passes ring attention over the ``seq`` axis instead.
    ``psum_axis``: when the projections are tensor-parallel (heads/FFN
    columns sharded), the row-parallel Wo/W2 matmuls are followed by a psum
    over that axis (set by the shard_map caller; None = single device).
    """
    def maybe_psum(x):
        return jax.lax.psum(x, psum_axis) if psum_axis else x

    u = layer_norm(h, p["ln1_g"], p["ln1_b"])
    q = split_heads(u @ p["Wq"], n_heads)
    k = split_heads(u @ p["Wk"], n_heads)
    v = split_heads(u @ p["Wv"], n_heads)
    if attention_fn is None:
        attention_fn = lambda q, k, v: flash_mha(q, k, v, causal)
    att = maybe_psum(merge_heads(attention_fn(q, k, v)) @ p["Wo"]) + p["bo"]
    h = h + att
    u = layer_norm(h, p["ln2_g"], p["ln2_b"])
    f = jax.nn.gelu(u @ p["W1"] + p["b1"])
    h = h + maybe_psum(f @ p["W2"]) + p["b2"]
    return h


def block_kv_project(p: Dict[str, Array], h: Array,
                     n_heads: int) -> tuple:
    """First half of the pre-LN block, split out for the decode path
    (serving/decode.py): q/k/v head projections of LN(h), so the caller
    can write k/v into the paged cache BEFORE attention runs against the
    gathered full-length view (ops/kv_cache.py).  Returns (q, k, v) as
    [B, H, T, d_head]."""
    u = layer_norm(h, p["ln1_g"], p["ln1_b"])
    return (split_heads(u @ p["Wq"], n_heads),
            split_heads(u @ p["Wk"], n_heads),
            split_heads(u @ p["Wv"], n_heads))


def block_finish(p: Dict[str, Array], h: Array, att_heads: Array, *,
                 psum_axis: Optional[str] = None) -> Array:
    """Second half of the pre-LN block: output projection + residual +
    FFN.  Same math as the tail of ``block_apply``; the decode
    prefill/step/re-encode paths all share it so their per-position
    bits agree by construction.  ``psum_axis``: the tensor-parallel
    decode path (parallel/transformer.py) passes ``att_heads`` holding
    only the LOCAL head group and a row-slice of ``Wo`` in ``p`` — the
    partial output projections psum over that axis before bias +
    residual.  Every shard runs the identical psum, so the per-shard
    decode-vs-reencode bit contract holds layout-for-layout."""
    m = merge_heads(att_heads) @ p["Wo"]
    if psum_axis is not None:
        m = jax.lax.psum(m, psum_axis)
    h = h + (m + p["bo"])
    u = layer_norm(h, p["ln2_g"], p["ln2_b"])
    f = jax.nn.gelu(u @ p["W1"] + p["b1"])
    return h + f @ p["W2"] + p["b2"]


def paged_decode_program(*, embed: Callable, pos: Callable, blocks: Callable,
                         head: Callable, heads: tuple, n_layers: int,
                         vocab_size: int, pos_rows: int, page_size: int,
                         max_len: Optional[int] = None,
                         psum_axis: Optional[str] = None):
    """The ``ops/kv_cache.DecodeProgram`` (its docstring has each entry
    point's contract) of a model made of this block, built ONCE for all
    of them.  A model describes itself with callables and numbers:
    ``embed(params, ids) -> rows``, ``pos(params) -> [pos_rows, D]``,
    ``blocks(params) -> [block_params trees]`` (cut to the heads this
    device holds), ``head(params, h) -> logits``, ``heads = (H, d_head)``
    of a cached row, ``psum_axis`` for ``block_finish``.  ``max_len``
    (default: the position table, whole pages) is the FIXED key length L.

    Each entry point forms its first hidden state and its page table,
    names the writer of its rows and how they attend, and calls
    ``layers``.  ``prefill`` / ``prefill_at`` (a bucket of query rows)
    attend as ``reencode`` does, ``det_attention`` over all L keys under
    a causal bias; ``step`` / ``spec_step`` / ``step_multi`` (one to a
    few rows a slot) through ``ops/paged_attention.py``, which reads only
    the pages a slot holds and sums in another order.  What that gives
    against ``reencode`` of the same tokens: equal tokens, and logits
    within ``tests/_decode_checks.py``'s limit (ROADMAP C1); one entry
    point against itself (co-batched, retried, fused, ``spec_step``
    against ``step``): equal bits."""
    from ..ops.kv_cache import (
        NEG_INF, DecodeProgram, det_attention, write_prefill, write_step,
        write_tokens,
    )
    from ..ops.paged_attention import paged_attention, window_attention
    from ..ops.sampling import sample_tokens

    if max_len is None:
        max_len = (pos_rows // page_size) * page_size
    if max_len % page_size or not (0 < max_len <= pos_rows):
        raise ValueError(
            f"max_len {max_len} must be a positive multiple of "
            f"page_size {page_size} and <= the position table "
            f"({pos_rows})")
    L = int(max_len)
    n_heads = heads[0]

    def layers(params, k_pages, v_pages, h, write, attend):
        # each layer stores its new rows (``write(pages, layer, kv
        # [B,H,T,d])``) BEFORE ``attend(q, k_pages, v_pages, layer)``
        # reads them back among the slot's rows
        for i, bp in enumerate(blocks(params)):
            q, k, v = block_kv_project(bp, h, n_heads)
            k_pages = write(k_pages, i, k)
            v_pages = write(v_pages, i, v)
            h = block_finish(bp, h, attend(q, k_pages, v_pages, i),
                             psum_axis=psum_axis)
        return k_pages, v_pages, head(params, h)

    def held(pt, lens):
        # few query rows a slot: over the ``lens`` rows each slot holds
        # (the new ones counted; 0 for a masked slot)
        return lambda q, k_pages, v_pages, i: paged_attention(
            q, k_pages, v_pages, i, pt, lens, heads)

    def one_slot(params, k_pages, v_pages, page_table_row, h, bias, n_real,
                 offset):
        # rows at offset..offset+Tb-1 -> the last REAL one's logits; pad
        # rows' K/V are garbage-but-finite, masked until overwritten
        k_pages, v_pages, lgs = layers(
            params, k_pages, v_pages, h,
            lambda pages, i, kv: write_prefill(
                pages, i, page_table_row, kv.transpose(0, 2, 1, 3)[0],
                offset),
            lambda q, k_pages, v_pages, i: window_attention(
                q, k_pages, v_pages, i, page_table_row[None], bias, heads))
        return k_pages, v_pages, lgs[0, n_real - 1]

    def prefill(params, k_pages, v_pages, page_table_row, tokens, n_real):
        tb = tokens.shape[0]
        h = (embed(params, tokens) + pos(params)[:tb])[None]
        bias = jnp.where(
            jnp.arange(L, dtype=jnp.int32)[None, :]
            <= jnp.arange(tb, dtype=jnp.int32)[:, None],
            0.0, NEG_INF)[None, None]                   # [1,1,Tb,L]
        return one_slot(params, k_pages, v_pages, page_table_row, h, bias,
                        n_real, 0)

    def prefill_at(params, k_pages, v_pages, page_table_row, tokens, n_real,
                   offset):
        # same per-row ops as ``prefill``: the position gather reads the
        # rows its slice does
        tb = tokens.shape[0]
        pos_abs = offset + jnp.arange(tb, dtype=jnp.int32)
        h = (embed(params, tokens)
             + pos(params)[jnp.clip(pos_abs, 0, pos_rows - 1)])[None]
        bias = jnp.where(
            jnp.arange(L, dtype=jnp.int32)[None, :]
            <= pos_abs[:, None], 0.0, NEG_INF)[None, None]
        return one_slot(params, k_pages, v_pages, page_table_row, h, bias,
                        n_real, offset)

    def step(params, k_pages, v_pages, page_table, tokens, positions,
             active):
        # a masked slot's table row is zeroed, so its write goes to the
        # scratch page and ONE program serves any active subset
        h = (embed(params, tokens) + pos(params)[positions])[:, None]
        pt = jnp.where(active[:, None], page_table, 0)
        k_pages, v_pages, lgs = layers(
            params, k_pages, v_pages, h,
            lambda pages, i, kv: write_step(pages, i, pt, positions,
                                            kv[:, :, 0]),
            held(pt, jnp.where(active, positions + 1, 0)))
        return k_pages, v_pages, lgs[:, 0]

    def spec_step(params, k_pages, v_pages, page_table, tokens, positions,
                  active):
        # rejected rows are garbage-but-finite and stay masked until the
        # next round overwrites them
        pos_abs = positions[:, None] + jnp.arange(tokens.shape[1],
                                                  dtype=jnp.int32)
        h = (embed(params, tokens)
             + pos(params)[jnp.clip(pos_abs, 0, pos_rows - 1)])
        pt = jnp.where(active[:, None], page_table, 0)
        return layers(
            params, k_pages, v_pages, h,
            lambda pages, i, kv: write_tokens(pages, i, pt, positions,
                                              kv.transpose(0, 2, 1, 3)),
            held(pt, jnp.where(active, positions + tokens.shape[1], 0)))

    def step_multi(params, k_pages, v_pages, page_table, tokens, positions,
                   active, temps, top_ks, top_ps, seeds, steps, budgets,
                   eos_id, horizon):
        # a scan of ``step``'s work and the engine's own sampler, keyed
        # ``fold_in(seed, steps + j)`` as its per-step sampler is.  A
        # slot that stops leaves ``alive``: its table row zeroes, so
        # live slots' bits match H plain steps.  Under tensor
        # parallelism post-psum h is replicated: every shard draws the
        # SAME token
        def body(carry, j):
            k_pages, v_pages, tok, alive = carry
            pos_j = positions + j
            h = (embed(params, tok)
                 + pos(params)[jnp.clip(pos_j, 0, pos_rows - 1)])[:, None]
            pt = jnp.where(alive[:, None], page_table, 0)
            k_pages, v_pages, lgs = layers(
                params, k_pages, v_pages, h,
                lambda pages, i, kv: write_step(pages, i, pt, pos_j,
                                                kv[:, :, 0]),
                held(pt, jnp.where(alive, pos_j + 1, 0)))
            lgs = lgs[:, 0]
            nxt, fin = sample_tokens(lgs, temps, top_ks, top_ps, seeds,
                                     steps + j)
            alive = alive & fin & (nxt != eos_id) & (j + 1 < budgets)
            return (k_pages, v_pages, nxt, alive), (nxt, fin, lgs)

        (k_pages, v_pages, _, _), (toks, fins, lgs) = jax.lax.scan(
            body, (k_pages, v_pages, tokens, active), horizon)
        return k_pages, v_pages, toks, fins, lgs

    def reencode(params, tokens):
        # the layers with no pool: the bit-identity oracle
        t = tokens.shape[1]
        h = embed(params, tokens) + pos(params)[:t]
        bias = jnp.where(
            jnp.arange(t, dtype=jnp.int32)[None, :]
            <= jnp.arange(t, dtype=jnp.int32)[:, None],
            0.0, NEG_INF)[None, None]
        for bp in blocks(params):
            q, k, v = block_kv_project(bp, h, n_heads)
            h = block_finish(bp, h, det_attention(q, k, v, bias),
                             psum_axis=psum_axis)
        return head(params, h)

    return DecodeProgram(
        prefill=prefill, step=step, reencode=reencode,
        n_layers=n_layers, n_heads=n_heads, d_head=heads[1],
        vocab_size=vocab_size, max_len=L, page_size=page_size,
        pages_per_slot=L // page_size,
        prefill_at=prefill_at, spec_step=spec_step, step_multi=step_multi,
        held_pages=True)


@register_layer
@dataclasses.dataclass
class TransformerBlock(Layer):
    """Pre-LN decoder block as a single MLN layer [B,T,D] → [B,T,D].

    Homogeneous by construction, so N of these stack into the pipeline's
    stage axis (parallel/pipeline.py) without any repartitioning.
    """

    d_model: int = 0
    n_heads: int = 8
    d_ff: int = 0              # 0 → 4*d_model
    causal: bool = True
    kernel: str = "flash"      # "flash" | "xla"

    wants = "rnn"

    def infer_nin(self, in_type: InputType) -> None:
        if not self.d_model:
            self.d_model = in_type.size
        if not self.d_ff:
            self.d_ff = 4 * self.d_model

    def output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.d_model, in_type.timesteps)

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        return block_params(rng, self.d_model, self.n_heads,
                            self.d_ff or 4 * self.d_model, dtype, self._winit())

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        x = self._maybe_dropout(x, train, rng)
        if mask is not None or self.kernel == "xla":
            att_mask = mask[:, None, None, :] if mask is not None else None
            attention_fn = lambda q, k, v: mha(q, k, v, causal=self.causal,
                                               mask=att_mask)
        else:
            attention_fn = None
        y = block_apply(params, x, self.n_heads, causal=self.causal,
                        attention_fn=attention_fn)
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return ForwardOut(y, state, mask)


@register_layer
@dataclasses.dataclass
class PositionalEmbedding(Layer):
    """Learned absolute positions added to the sequence embedding."""

    max_len: int = 512
    d_model: int = 0

    wants = "rnn"

    def infer_nin(self, in_type: InputType) -> None:
        if not self.d_model:
            self.d_model = in_type.size

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        return {"P": 0.02 * jax.random.normal(rng, (self.max_len, self.d_model),
                                              dtype)}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        t = x.shape[1]
        return ForwardOut(x + params["P"][:t].astype(x.dtype), state, mask)


def TransformerLM(vocab_size: int = 256, n_layers: int = 4, d_model: int = 256,
                  n_heads: int = 8, d_ff: int = 0, max_len: int = 512,
                  seed: int = 42, updater=None, kernel: str = "flash",
                  dtype=None) -> MultiLayerNetwork:
    """Decoder-only LM: EmbeddingSequence + positions + N blocks + head."""
    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(updater or Adam(lr=3e-4))
         .gradient_normalization(GradientNormalization.CLIP_L2_PER_LAYER, 1.0)
         .layer(EmbeddingSequence(n_in=vocab_size, n_out=d_model))
         .layer(PositionalEmbedding(max_len=max_len, d_model=d_model)))
    for _ in range(n_layers):
        b.layer(TransformerBlock(d_model=d_model, n_heads=n_heads, d_ff=d_ff,
                                 kernel=kernel))
    b.layer(RnnOutputLayer(n_out=vocab_size, activation="softmax", loss="mcxent"))
    b.set_input_type(InputType.recurrent(vocab_size, max_len))
    if dtype is not None:
        b.dtype(*dtype) if isinstance(dtype, tuple) else b.dtype(dtype)
    net = MultiLayerNetwork(b.build())
    net.init()
    return net


class TransformerDecodeAdapter:
    """Serve a single-chip ``TransformerLM`` MultiLayerNetwork through
    ``serving.DecodeEngine``: the same ``params`` + ``decode_program()``
    surface ShardedTransformerLM exposes, built from the MLN layer stack
    (EmbeddingSequence, PositionalEmbedding, TransformerBlock × N,
    RnnOutputLayer).  The wrapped network itself is untouched: its
    one-shot ``output``/``predict`` path keeps its own jit programs (the
    no-behavior-change regression in tests/test_decode.py)."""

    def __init__(self, net: MultiLayerNetwork):
        layers = net.conf.layers
        ok = (len(layers) >= 4
              and isinstance(layers[0], EmbeddingSequence)
              and isinstance(layers[1], PositionalEmbedding)
              and all(isinstance(l, TransformerBlock) for l in layers[2:-1])
              and isinstance(layers[-1], RnnOutputLayer))
        if not ok:
            raise ValueError(
                "TransformerDecodeAdapter needs the TransformerLM stack "
                "(EmbeddingSequence, PositionalEmbedding, TransformerBlock "
                "x N, RnnOutputLayer); got "
                + ", ".join(type(l).__name__ for l in layers))
        cd = getattr(net.conf, "compute_dtype", None)
        if cd is not None and jnp.dtype(cd) != jnp.float32:
            raise NotImplementedError(
                "decode serves the f32 params path; compute_dtype "
                f"{cd!r} would break the re-encode bit-identity contract")
        self.net = net
        self._embed_lay = layers[0]
        self._out_lay = layers[-1]
        self._n_blocks = len(layers) - 3
        self.n_heads = int(layers[2].n_heads)
        self.vocab_size = int(self._out_lay.n_out)
        self.params = {
            "embed": net.params[0], "pos": net.params[1],
            "blocks": [net.params[2 + i] for i in range(self._n_blocks)],
            "head": net.params[len(layers) - 1],
        }

    def decode_program(self, page_size: int = 16,
                       max_len: Optional[int] = None):
        """``paged_decode_program`` of the MLN layer stack: the embedding
        layer's own activation and bias, a list of block trees, and the
        output layer's pre-softmax head (no final norm)."""
        embed_lay, out_lay = self._embed_lay, self._out_lay
        d_model = int(self.params["embed"]["W"].shape[1])

        def embed(params, idx):
            y = params["embed"]["W"][idx]
            if embed_lay.has_bias:
                y = y + params["embed"]["b"]
            return embed_lay._act(y)

        def head(params, h):
            y = h @ params["head"]["W"]
            if out_lay.has_bias:
                y = y + params["head"]["b"]
            return y          # pre-softmax logits (RnnOutputLayer._pre)

        return paged_decode_program(
            embed=embed, pos=lambda params: params["pos"]["P"],
            blocks=lambda params: params["blocks"], head=head,
            heads=(self.n_heads, d_model // self.n_heads),
            n_layers=self._n_blocks, vocab_size=self.vocab_size,
            pos_rows=int(self.params["pos"]["P"].shape[0]),
            page_size=page_size, max_len=max_len)
