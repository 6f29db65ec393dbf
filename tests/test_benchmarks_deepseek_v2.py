"""Tier-1 runs the benchmark's own tests: ``benchmarks/tests/test_deepseek_v2.py``.

Named to sort early: under ``--dist loadfile`` a file is one unit of work, handed
out in collection order, and the longest of these must not start last.
"""

from benchmarks.tests.test_deepseek_v2 import *  # noqa: F401,F403
