"""The five ``startup_*`` per-layer metrics (PR 39), where tier-1
collects them: the cases of ``benchmarks/tests/test_startup_metrics.py``,
run from here because tier-1 collects ``tests/`` and not
``benchmarks/tests`` (the precedents are
``tests/test_paged_attention_metrics.py`` and
``tests/test_benchmark_windows.py``)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_startup_metrics import *  # noqa: E402,F401,F403
