"""Plain reference of configuration ``gpt2-medium`` (trained in bfloat16).

The mathematics is ``gpt2_reference.py`` beside this file, in float32 at
``highest`` matmul precision.  The configuration states bfloat16 compute,
so its control is the nearest precision below: fp8 products (e4m3
operands, e5m2 gradients, one scale per tensor)."""

from gpt2_reference import *  # noqa: F401,F403 - this file IS that reference

STATED_PRECISION = "bfloat16"
CONTROL_PRECISION = "fp8"
