"""Tier-1 runs the benchmark's own tests: ``benchmarks/tests/test_loop_spans.py``."""

from benchmarks.tests.test_loop_spans import *  # noqa: F401,F403
