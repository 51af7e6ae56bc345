"""Plain reference of configuration ``gpt2-large`` (served from float32
weights and a float32 KV pool).

The mathematics is ``gpt2_reference.py`` beside this file, in float32 at
``highest`` matmul precision.  The configuration states float32, so its
control is the whole forward pass in bfloat16 (weights, activations and
logits), the step that would tempt a later PR.  On the TPU a float32
product at default precision is one bfloat16 pass with float32
accumulation (PR 21 measured it), so the program's own logits already
lie some way from this reference, and the bfloat16 pass about twice as
far: the served tokens alone do not tell the two apart, the logits the
engine echoes do (``served_logit_mse``; PERF.md, section 2).  A second
control, fp8 operands (e4m3, one scale per tensor), fails every number."""

from gpt2_reference import *  # noqa: F401,F403 - this file IS that reference

STATED_PRECISION = "float32"
CONTROL_PRECISION = "bfloat16"
