"""Tier-1 collects ``perfbench/tests/test_trace_reduce.py`` as it is (one ``loadfile`` unit)."""

from perfbench.tests.test_trace_reduce import *  # noqa: F401,F403
