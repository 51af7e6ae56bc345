"""Shared example bootstrap.

Every example is runnable standalone (``python examples/01_....py``) on
whatever accelerator JAX finds; CI runs them with ``JAX_PLATFORMS=cpu``.
"""

import os
import sys

# make `python examples/xx.py` work from a source checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def banner(title: str) -> None:
    print(f"\n=== {title} ===")
