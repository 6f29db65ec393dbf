"""Tier-1 runs the benchmark's own tests: ``benchmarks/tests/test_pass_log.py``."""

from benchmarks.tests.test_pass_log import *  # noqa: F401,F403
