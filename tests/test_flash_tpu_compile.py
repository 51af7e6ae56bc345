"""The three flash kernels compiled for a DESCRIBED TPU v5e, without one.

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

from deeplearning4j_tpu.ops import attention


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
        one_chip, entry):
    """The decode step and a 512-token prefill chunk of the benchmark's
    ``kimi-k2-instruct`` configuration (16 slots, 4,096 positions, bf16)
    compile for one v5e chip, fit its memory, update the donated pool in
    place, and keep less in temporaries than one pool holds, so no copy
    of the pool is among them: a 576-lane row, ``pool[layer][table]``
    and a scatter a layer each cost whole-pool copies a call (PERF.md
    §6, PR 27)."""
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
