"""Dump compiled-step diagnostics: cost analysis (flops/bytes), memory
analysis, and an HLO op histogram — plus STRUCTURAL assertions for the
pallas kernel programs.

Usage: python hlo_probe.py <tree> <tag> [program]

Programs:
  lenet (default)   the LeNet bench step (histogram only, no assertions)
  fused_update      the fused Adam update (ops/update_kernel.py)

For the pallas program the probe asserts the landing actually
happened structurally — the failure mode being a silently-fallen-back
kernel that still passes parity tests:

  * exactly ONE pallas_call equation in the traced jaxpr (recursively,
    including lax.cond branches — interpret-mode lowering erases the op
    from compiled CPU HLO, so the jaxpr is where the claim is checkable
    on every backend);
  * the program contains no sort;
  * no transpose equations and no stray convert PAIRS (a convert whose
    input is itself a convert — a round trip the flat f32 layout should
    never need).

Exit code 1 with a clear message when a structural assertion fails.
"""
import collections
import json
import re
import sys

tree, tag = sys.argv[1], sys.argv[2]
program = sys.argv[3] if len(sys.argv) > 3 else "lenet"
sys.path.insert(0, tree)

import numpy as np
import jax
import jax.numpy as jnp
import jax.random as jrandom


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for item in vals:
            if hasattr(item, "jaxpr"):      # ClosedJaxpr
                yield item.jaxpr
            elif hasattr(item, "eqns"):     # raw Jaxpr
                yield item


def count_primitive(jaxpr, name: str) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            total += 1
        for sub in _sub_jaxprs(eqn):
            total += count_primitive(sub, name)
    return total


def convert_pairs(jaxpr) -> int:
    """Stray convert chains: convert eqns whose input is itself produced
    by a convert (recursively per sub-jaxpr scope)."""
    producer = {}
    pairs = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            src = eqn.invars[0]
            if producer.get(id(src)) == "convert_element_type":
                pairs += 1
        for out in eqn.outvars:
            producer[id(out)] = eqn.primitive.name
        for sub in _sub_jaxprs(eqn):
            pairs += convert_pairs(sub)
    return pairs


def assert_pallas_structure(jaxpr, out: dict):
    out["pallas_calls"] = count_primitive(jaxpr, "pallas_call")
    out["transposes_jaxpr"] = count_primitive(jaxpr, "transpose")
    out["convert_pairs"] = convert_pairs(jaxpr)
    out["sorts"] = (count_primitive(jaxpr, "sort")
                    + count_primitive(jaxpr, "top_k"))
    errs = []
    if out["pallas_calls"] != 1:
        errs.append(f"expected exactly 1 pallas_call, found "
                    f"{out['pallas_calls']}")
    if out["transposes_jaxpr"]:
        errs.append(f"{out['transposes_jaxpr']} stray transpose(s)")
    if out["convert_pairs"]:
        errs.append(f"{out['convert_pairs']} stray convert pair(s)")
    if out["sorts"]:
        errs.append(f"{out['sorts']} sort(s) in a sort-free program")
    if errs:
        print(json.dumps({"tag": tag, "program": program,
                          "structure_ok": False, "errors": errs, **out}))
        raise SystemExit(1)
    out["structure_ok"] = True


if program == "fused_update":
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.ops import update_kernel

    update_kernel.ENABLED = True
    update_kernel.FORCE_JNP = False
    rng = np.random.default_rng(0)
    params = {f"l{i}": {"W": jnp.asarray(rng.normal(size=(256, 256)),
                                         jnp.float32)}
              for i in range(4)}
    upd = Adam(lr=1e-3)
    state = {"m": jax.tree_util.tree_map(jnp.zeros_like, params),
             "v": jax.tree_util.tree_map(jnp.zeros_like, params)}
    it = jnp.asarray(0.0, jnp.float32)

    def fn(p, g, s, i):
        return update_kernel.fused_apply("adam", upd, p, g, s, i)

    jaxpr = jax.make_jaxpr(fn)(params, params, state, it).jaxpr
    out = {"tag": tag, "program": program}
    assert_pallas_structure(jaxpr, out)
    print(json.dumps(out))
    raise SystemExit(0)

from deeplearning4j_tpu.models import LeNet
from deeplearning4j_tpu.nn.updaters import Nesterovs

batch = 256
net = LeNet(height=32, width=32, channels=3, num_classes=10,
            updater=Nesterovs(lr=0.01, momentum=0.9))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(batch, 32, 32, 3)).astype(np.float32))
y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)])
if net._jit_step is None:
    net._jit_step = net._make_step()
args = (net.params, net.state, net.opt_state, jnp.asarray(0, jnp.int32),
        x, y, jrandom.PRNGKey(0), None, None)
lowered = net._jit_step.lower(*args)
compiled = lowered.compile()
out = {"tag": tag}
try:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    out["flops"] = ca.get("flops")
    out["bytes"] = ca.get("bytes accessed")
except Exception as e:
    out["cost_err"] = str(e)
try:
    ma = compiled.memory_analysis()
    out["temp_mb"] = round(ma.temp_size_in_bytes / 1e6, 2)
    out["output_mb"] = round(ma.output_size_in_bytes / 1e6, 2)
except Exception as e:
    out["mem_err"] = str(e)
hlo = compiled.as_text()
ops = collections.Counter(re.findall(r"= \w+\[?[^ ]* (\w+)\(", hlo))
out["n_hlo_lines"] = hlo.count("\n")
out["fusions"] = ops.get("fusion", 0)
out["convs"] = ops.get("convolution", 0)
out["copies"] = ops.get("copy", 0) + ops.get("copy-start", 0)
out["top_ops"] = dict(ops.most_common(12))
print(json.dumps(out))
with open(f"/tmp/ab_hlo_{tag}.txt", "w") as f:
    f.write(hlo)
