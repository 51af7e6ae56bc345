"""The Mosaic kernels (flash attention's three, the decode step's paged
attention and latent attention, the expert layers' grouped feed-forward)
compiled for a DESCRIBED TPU v5e, without one.

Interpret mode (tests/test_attention.py) cannot see what Mosaic refuses: a
slice not aligned to the tiling, more VMEM than a kernel may use.  The
TPU's compiler is installed here and compiles for a chip that is described
and not attached, about two seconds a case.  Nothing runs: a compile that
passes is no chip run and gives no time.

The topology is described inside a fixture and never while a module is
imported: only one process at a time may load the TPU's library, and every
pytest-xdist worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops import (attention, grouped_ffn, latent_attention,
                                    paged_attention)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The code asks ``jax.default_backend()`` and would take its CPU
    branch (the interpreter) here: steer it in the test."""
    monkeypatch.setattr(attention, "interpret", lambda: False)
    monkeypatch.setattr(paged_attention, "interpret", lambda: False)
    monkeypatch.setattr(latent_attention, "interpret", lambda: False)
    monkeypatch.setattr(grouped_ffn, "interpret", lambda: False)


# B, H, T, S, D, dtype, causal, kmask
CASES = {
    "train-1k": (12, 16, 1024, 1024, 64, "bfloat16", True, False),
    "train-1k-noncausal-kmask": (2, 16, 1024, 1024, 64, "bfloat16", False, True),
    "T4096-kmask": (2, 8, 4096, 4096, 64, "bfloat16", True, True),
    "T2048-f32": (1, 2, 2048, 2048, 64, "float32", True, False),
    "T1024-f32-d128": (1, 6, 1024, 1024, 128, "float32", True, True),
    "T8192-d128-in-parts": (1, 4, 8192, 8192, 128, "bfloat16", True, True),
    "T128-many-heads": (96, 16, 128, 128, 64, "bfloat16", True, False),
    "T192-whole-axis": (2, 4, 192, 192, 64, "bfloat16", True, True),
    "T64-d16-f32": (2, 4, 64, 64, 16, "float32", True, True),
    "cross-T512-S1024": (2, 4, 512, 1024, 64, "bfloat16", False, True),
    "causal-cross-T256-S512": (2, 4, 256, 512, 64, "bfloat16", True, False),
    "T1536-S768": (1, 4, 1536, 768, 64, "bfloat16", True, False),
    "causal-cross-T2048-S1024": (1, 4, 2048, 1024, 64, "bfloat16", True, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_and_backward_compile_for_v5e(one_chip, as_on_tpu, case):
    B, H, T, S, D, dtype, causal, masked = CASES[case]
    assert attention.flash_tiles(T, S, D, jnp.dtype(dtype).itemsize,
                                 B * H) is not None
    q = jax.ShapeDtypeStruct((B, H, T, D), dtype, sharding=one_chip)
    k = jax.ShapeDtypeStruct((B, H, S, D), dtype, sharding=one_chip)
    km = (jax.ShapeDtypeStruct((B, S), jnp.float32, sharding=one_chip)
          if masked else None)

    def grads(q, k, v, km):
        return jax.grad(lambda q, k, v: jnp.sum(attention.flash_mha(
            q, k, v, causal, kmask=km).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    hlo = jax.jit(grads).lower(q, k, k, km).compile().as_text()
    assert hlo.count("tpu_custom_call") == 3


# -- the latent decode programs at Kimi-K2's widths -------------------------------

@pytest.mark.parametrize("entry", ["step", "step_multi", "prefill_at"])
def test_latent_decode_program_compiles_for_v5e_without_a_pool_copy(
        one_chip, as_on_tpu, entry):
    """The decode step and a 512-token prefill chunk of the benchmark's
    ``kimi-k2-instruct`` configuration (16 slots, 4,096 positions, bf16)
    compile for one v5e chip, fit its memory, update the donated pool in
    place, and keep less in temporaries than one pool holds, so no copy
    of the pool is among them: a 576-lane row, ``pool[layer][table]``
    and a scatter a layer each cost whole-pool copies a call (PERF.md
    §6, PR 27).

    The step holds ONE Mosaic call a layer (ops/latent_attention.py),
    which reads the pool in place, and nothing shaped like the gathered
    window, 84 MB written a layer a step (PERF.md section 6, PR 42); the
    chunk walks the pages held in blocks and holds no Mosaic call."""
    import json

    from deeplearning4j_tpu.models import latent_moe
    from deeplearning4j_tpu.models.arch import LMArch
    from deeplearning4j_tpu.ops.kv_cache import alloc_pools

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "kimi-k2-instruct.json")) as f:
        cfg = json.load(f)
    slots, page = cfg["program"]["max_slots"], cfg["program"]["page_size"]
    arch = LMArch.from_config(cfg, max_len=cfg["program"]["max_len"],
                              param_dtype="bfloat16")
    prog = latent_moe.decode_program(arch, page, arch.max_len)
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: latent_moe.init_params(jax.random.PRNGKey(0), arch,
                                       jnp.bfloat16)))
    pool, none = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: tuple(alloc_pools(prog, 1 + slots * prog.pages_per_slot))))
    assert pool.shape == (8, 4097, 16, 640) and pool.dtype == jnp.bfloat16
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    if entry == "step":
        args = (i32(slots, prog.pages_per_slot), i32(slots), i32(slots),
                jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip))
        fn = prog.step
    elif entry == "step_multi":
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,
                                              sharding=one_chip)
        args = (i32(slots, prog.pages_per_slot), i32(slots), i32(slots),
                jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
                f32(slots), i32(slots), f32(slots),
                jax.ShapeDtypeStruct((slots,), jnp.uint32, sharding=one_chip),
                i32(slots), i32(slots), i32(),
                i32(cfg["program"]["decode_horizon"]))
        fn = prog.step_multi
    else:
        args = (i32(prog.pages_per_slot), i32(512), i32(), i32())
        fn = prog.prefill_at
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, pool, none, *args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    assert mem.alias_size_in_bytes >= 8 * 4097 * 16 * 640 * 2   # in place
    # the program's temporaries are smaller than the pool: no copy of it
    assert mem.temp_size_in_bytes < 8 * 4097 * 16 * 640 * 2
    import re
    hlo = compiled.as_text()
    # (XLA's own grouped products are Mosaic calls too: by the name)
    mosaic_calls = len(re.findall(
        r"%latent_attention[.\d]* = \S+ custom-call\(.*tpu_custom_call", hlo))
    if entry == "prefill_at":
        assert mosaic_calls == 0
        return
    # (a fused horizon's scan holds its body once)
    assert mosaic_calls == prog.n_layers == 8
    for gathered in ("bf16[%d,%d,640]" % (slots, arch.max_len),
                     "bf16[%d,%d,640]" % (arch.max_len, slots),
                     "bf16[%d,16,640]" % (slots * prog.pages_per_slot),
                     "f32[%d,64,%d]" % (slots, arch.max_len)):
        assert gathered not in hlo, gathered


# -- the expert layers' grouped feed-forward at the five expert cells' shapes -------

# cell -> (rows a decode step sorts (slots x k), rows of the largest
# prefill chunk (512 x k), d, f, experts held)
EXPERT_CALLS = {
    "lfm2-24b-a2b": (512, 2048, 2048, 1536, 64),
    "granite-4.0-h-small": (480, 5120, 4096, 768, 36),
    "solar-open2-250b": (256, 4096, 4096, 1280, 40),
    "kimi-k2-instruct": (128, 4096, 7168, 2048, 12),
    "keye-vl-2-30b-a3b": (64, 4096, 2048, 768, 128),
}


def _expert_shapes(cell, call):
    """What the configuration's file says, so that the table above cannot
    drift from it."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs", cell + ".json")) as f:
        cfg = json.load(f)
    step, chunk, d, f, held = EXPERT_CALLS[cell]
    k, prog = cfg["num_experts_per_tok"], cfg["program"]
    assert step == prog["max_slots"] * k and chunk == prog["prefill_chunk"] * k
    assert d == cfg["hidden_size"] and held == cfg["n_routed_experts"]
    assert f == cfg.get("moe_intermediate_size", cfg["intermediate_size"])
    return (step if call == "step" else chunk), d, f, held


@pytest.mark.parametrize("call", ["step", "chunk"])
@pytest.mark.parametrize("cell", sorted(EXPERT_CALLS))
def test_grouped_ffn_compiles_for_v5e_at_the_expert_cells_shapes(
        one_chip, as_on_tpu, cell, call):
    """Mosaic's alignment and VMEM limits show only here: the kernel at
    each expert cell's decode step and largest prefill chunk (bf16), ONE
    Mosaic call where three grouped products stood, within the VMEM its
    tiles say."""
    m, d, f, held = _expert_shapes(cell, call)
    bf16 = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    x = bf16(m, d)
    assert grouped_ffn.kept_path(x, bf16(held, d, f)) is None
    tiles = grouped_ffn.ffn_tiles(m, d, f, held, 2)
    assert tiles.vmem_bytes <= 48 * 1024 * 1024
    hlo = jax.jit(grouped_ffn.grouped_ffn).lower(
        x, bf16(held, d, f), bf16(held, d, f), bf16(held, f, d),
        jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    assert hlo.count("tpu_custom_call") == 1
    assert "%grouped_ffn" in hlo and "ragged" not in hlo


def test_grouped_ffn_compiles_for_v5e_with_float32_weights(one_chip,
                                                           as_on_tpu):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    hlo = jax.jit(grouped_ffn.grouped_ffn).lower(
        f32(96, 512), f32(8, 512, 256), f32(8, 512, 256), f32(8, 256, 512),
        jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    assert hlo.count("tpu_custom_call") == 1


def _keye_entry(one_chip, entry):
    """``(cfg, arch, prog, pools, lowered)`` of one entry point of the
    benchmark's ``keye-vl-2-30b-a3b`` configuration (8 slots, 16,384
    positions, bf16) on the described chip."""
    import json

    from deeplearning4j_tpu.models import sparse_gqa
    from deeplearning4j_tpu.models.arch import LMArch
    from deeplearning4j_tpu.ops.kv_cache import alloc_pools

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "keye-vl-2-30b-a3b.json")) as f:
        cfg = json.load(f)
    slots, page = cfg["program"]["max_slots"], cfg["program"]["page_size"]
    arch = LMArch.from_config(cfg, max_len=cfg["program"]["max_len"],
                              param_dtype="bfloat16")
    prog = sparse_gqa.decode_program(arch, page, arch.max_len)
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: sparse_gqa.init_params(jax.random.PRNGKey(0), arch,
                                       jnp.bfloat16)))
    k_pool, rest = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: tuple(alloc_pools(prog, 1 + slots * prog.pages_per_slot))))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    flags = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)
    step_args = (i32(slots, prog.pages_per_slot), i32(slots), i32(slots),
                 flags)
    fn, args = {
        "step": (prog.step, step_args),
        "step_multi": (prog.step_multi, step_args + (
            f32(slots), i32(slots), f32(slots),
            jax.ShapeDtypeStruct((slots,), jnp.uint32, sharding=one_chip),
            i32(slots), i32(slots), i32(),
            i32(cfg["program"]["decode_horizon"]))),
        "prefill_at": (prog.prefill_at, (
            i32(prog.pages_per_slot), i32(cfg["program"]["prefill_chunk"]),
            i32(), i32())),
    }[entry]
    lowered = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, k_pool, rest, *args)
    return cfg, arch, prog, (k_pool, rest), lowered


@pytest.mark.parametrize("entry", ["step", "step_multi", "prefill_at"])
def test_an_expert_program_lowers_the_grouped_ffn_once_a_shape(
        one_chip, as_on_tpu, entry):
    """ROADMAP A12: a kernel lowered at every call site is paid at every
    load (PR 45's: three calls a layer, ten layers, five programs, 0.95 s
    of the Granite cell's set-up and its refusal).  The Keye
    configuration's programs hold seven expert layers of one shape: the
    lowered text holds what follows the router ONCE, as a private
    function called seven times, and in it ONE Mosaic call
    (``step_multi`` scans its step: the same one)."""
    import re

    _, _, prog, _, lowered = _keye_entry(one_chip, entry)
    text = lowered.as_text()
    assert prog.n_layers == 7
    assert len(re.findall(r"func\.func private @_held_picks", text)) == 1
    assert len(re.findall(r"call @_held_picks", text)) == 7
    assert text.count("@tpu_custom_call") == 1
    assert text.count("ragged_dot") == 0


# -- the sparse decode programs at Keye-VL-2.0's widths ----------------------------

@pytest.mark.parametrize("entry", ["step", "step_multi", "prefill_at"])
def test_sparse_decode_program_compiles_for_v5e_without_a_pool_copy(
        one_chip, as_on_tpu, entry):
    """The decode step, the fused horizon and a prefill chunk (512
    tokens) of the benchmark's ``keye-vl-2-30b-a3b`` configuration (8 slots,
    16,384 positions, bf16) compile for one v5e chip, fit its memory,
    update the three donated pools in place and keep far less in
    temporaries than one pool holds.  In the compiled text every pool,
    the 128-lane index pool among them, keeps its natural layout (a
    64-lane row would be stored pages-minor and transposed in and out
    of every call), and the steps hold no K or V temporary of a slot's
    window: they gather the 2,048 chosen rows."""
    import re

    _, _, _, (k_pool, rest), lowered = _keye_entry(one_chip, entry)
    assert k_pool.shape == rest[0].shape == (7, 8193, 16, 512)
    assert rest[1].shape == (7, 8193, 16, 128)
    pool_bytes = 7 * 8193 * 16 * (512 + 512 + 128) * 2
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    assert mem.alias_size_in_bytes >= pool_bytes            # all three in place
    # a K or V pool is 940 MB: no copy of one (a 512-token chunk's key
    # table and one block of scores are 43 MB, a step's 26 to 33)
    assert mem.temp_size_in_bytes < 100e6
    text = compiled.as_text()
    layouts = set(re.findall(r"bf16\[7,8193,16,(?:512|128)\]\{([0-9,]*)", text))
    assert layouts == {"3,2,1,0"}, layouts                  # never pages-minor
    if entry != "prefill_at":
        # a slot's window of K or V rows, flat or by heads
        assert not re.search(r"bf16\[8,16384,(512|4,128)\]", text)
        assert "bf16[8,2048,512]" in text                   # the chosen rows


# -- the two-kind decode programs at Solar-Open2's widths ---------------------------

@pytest.mark.parametrize("entry", ["step_multi", "prefill_at"])
def test_linear_gqa_decode_program_compiles_for_v5e_in_place(
        one_chip, as_on_tpu, entry):
    """The fused horizon and a prefill chunk (512 tokens) of the
    benchmark's ``solar-open2-250b`` configuration (32 slots, 19,456
    positions, bf16 weights, float32 state) compile for one v5e chip, fit
    its memory and update the two donated pools AND the per-slot state in
    place.  The step's block walk gathers K and V blocks of FOUR slots
    (``linear_gqa.STEP_GROUP``), never of all 32, and keeps under a third
    of the 160 MB that one walk of every slot kept in temporaries."""
    import json

    from deeplearning4j_tpu.models import linear_gqa
    from deeplearning4j_tpu.models.arch import LMArch
    from deeplearning4j_tpu.ops.kv_cache import alloc_pools

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "solar-open2-250b.json")) as f:
        cfg = json.load(f)
    slots, page = cfg["program"]["max_slots"], cfg["program"]["page_size"]
    arch = LMArch.from_config(cfg, max_len=cfg["program"]["max_len"],
                              param_dtype="bfloat16")
    prog = linear_gqa.decode_program(arch, page, arch.max_len)
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: linear_gqa.init_params(jax.random.PRNGKey(0), arch,
                                       jnp.bfloat16)))
    k_pool, rest = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: tuple(alloc_pools(prog, 1 + slots * prog.pages_per_slot,
                                  slots=slots))))
    assert k_pool.shape == (1, 1 + 32 * 1216, 16, 1024)
    held = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves((k_pool, rest)))
    assert 2.9e9 < held < 3.0e9         # 2.55 GB of K and V, 0.42 of state
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    if entry == "step_multi":
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,
                                              sharding=one_chip)
        args = (i32(slots, prog.pages_per_slot), i32(slots), i32(slots),
                jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
                f32(slots), i32(slots), f32(slots),
                jax.ShapeDtypeStruct((slots,), jnp.uint32, sharding=one_chip),
                i32(slots), i32(slots), i32(),
                i32(cfg["program"]["decode_horizon"]))
        fn = prog.step_multi
    else:
        args = (i32(prog.pages_per_slot), i32(cfg["program"]["prefill_chunk"]),
                i32(), i32(), i32())
        fn = prog.prefill_at
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, k_pool, rest, *args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    assert mem.alias_size_in_bytes >= held              # pools and state in place
    if entry == "step_multi":
        assert mem.temp_size_in_bytes < 55e6
        text = compiled.as_text()
        group = linear_gqa.STEP_GROUP
        assert f"bf16[{group},64,16,1024]" in text      # four slots' block
        assert "bf16[32,64,16,1024]" not in text        # never all 32


# -- the GPT-2 decode programs at gpt2-large's widths -----------------------------

@pytest.fixture(scope="module")
def gpt2_large():
    """``ShardedTransformerLM`` as the benchmark's ``serve_lm`` builds it
    from ``gpt2-large.json``, its 3.1 GB of weights never drawn (the
    constructor runs under ``jax.eval_shape``), and the file's
    ``program`` (its ``max_slots`` is 4; the cases bring their own)."""
    import json

    from deeplearning4j_tpu.nn.updaters import Sgd
    from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "gpt2-large.json")) as f:
        cfg = json.load(f)
    built = []

    def build():
        built.append(ShardedTransformerLM(
            vocab_size=cfg["vocab_size"], n_layers=cfg["n_layer"],
            d_model=cfg["n_embd"], n_heads=cfg["n_head"],
            d_ff=cfg["n_inner"],
            mesh=build_mesh({"data": 1}, devices=jax.devices()[:1]),
            max_len=cfg["n_positions"], updater=Sgd(lr=0.0)))
        return built[0].params

    shapes = jax.eval_shape(build)
    lm, = built
    lm.params = shapes
    return lm, cfg["program"]


GPT2_DECODE_CASES = [("step", 4), ("step", 16), ("prefill", 16),
                     ("prefill_at", 16), ("spec_step", 16),
                     ("step_multi", 16)]
#: temporaries of ``step`` while it gathered every slot's window (PERF.md
#: section 6, PR 28), by slots: what the kernel may not exceed
STEP_TEMPORARIES_BEFORE = {4: 127e6, 16: 421e6}


@pytest.mark.parametrize("entry,slots", GPT2_DECODE_CASES)
def test_gpt2_decode_program_compiles_for_v5e_without_a_pool_copy(
        one_chip, as_on_tpu, gpt2_large, entry, slots):
    """Every entry point of ``ShardedTransformerLM.decode_program`` at
    GPT-2 large's widths (f32, 1,024 positions, pages of 16) compiles
    for one v5e chip at 4 and at 16 slots, fits its memory, gives both
    donated pools back in place and keeps less in temporaries than one
    pool holds, with no ``copy`` of a pool-shaped array: a pool stored
    ``[..., 20, 64]`` got the chip's pages-minor layout and was
    transposed in and out of every call, four 758 MB copies a step at 4
    slots and out of memory at 8 (PERF.md section 6, PR 28).

    The entry points of a few query rows a slot hold ONE Mosaic call a
    layer (ops/paged_attention.py), which reads the pool in place right
    after the layer's scatter wrote into it, and nothing shaped like the
    gathered window or its ``[.., 20, 64]`` relayout (PERF.md section 6,
    PR 30); the prefill forms keep the window and hold no Mosaic call."""
    import re

    from deeplearning4j_tpu.ops.kv_cache import alloc_pools

    lm, program = gpt2_large
    prog = lm.decode_program(page_size=program["page_size"],
                             max_len=program["max_len"])
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.tree_util.tree_map(on_chip, lm.params)
    pps = prog.pages_per_slot
    kp, vp = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: tuple(alloc_pools(prog, 1 + slots * pps))))
    assert kp.shape == vp.shape == (36, 1 + slots * 64, 16, 1280)
    pool_bytes = 4 * kp.size

    def arr(dtype, *s):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    i32 = lambda *s: arr(jnp.int32, *s)
    batch = (i32(slots, pps), i32(slots), i32(slots), arr(jnp.bool_, slots))
    args = {
        "step": batch,
        "prefill": (i32(pps), i32(128), i32()),
        "prefill_at": (i32(pps), i32(128), i32(), i32()),
        "spec_step": (i32(slots, pps), i32(slots, 4), i32(slots),
                      arr(jnp.bool_, slots)),
        "step_multi": batch + (
            arr(jnp.float32, slots), i32(slots), arr(jnp.float32, slots),
            arr(jnp.uint32, slots), i32(slots), i32(slots), i32(), i32(4)),
    }[entry]
    compiled = jax.jit(getattr(prog, entry), donate_argnums=(1, 2)).lower(
        params, kp, vp, *args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    assert mem.alias_size_in_bytes >= 2 * pool_bytes      # both in place
    assert mem.temp_size_in_bytes < pool_bytes
    hlo = compiled.as_text()
    pool_copy = re.compile(
        r"= f32\[%s\]\S* copy\(" % ",".join(map(str, kp.shape)))
    assert not pool_copy.findall(hlo)
    # nor is one layer's slice of a pool taken out before the gather, as
    # ``pool[layer][table]`` did 72 times a step (a third of the 16-slot
    # step on the chip): nothing has that shape
    layer_slice = "= f32[%s]" % ",".join(map(str, kp.shape[1:]))
    assert layer_slice not in hlo
    mosaic_calls = hlo.count('custom_call_target="tpu_custom_call"')
    if entry in ("prefill", "prefill_at"):
        assert mosaic_calls == 0
        return
    assert mosaic_calls == prog.n_layers == 36
    window = prog.max_len
    for gathered in ("f32[%d,%d,20,64]" % (slots, window),
                     "f32[%d,20,%d,64]" % (slots, window),
                     "f32[%d,%d,1280]" % (slots, window),
                     "f32[%d,16,1280]" % (slots * pps)):
        assert gathered not in hlo, gathered
    if entry == "step":
        assert mem.temp_size_in_bytes < STEP_TEMPORARIES_BEFORE[slots] / 4
