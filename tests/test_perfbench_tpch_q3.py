"""Tier-1 collects ``perfbench/tests/test_tpch_q3.py`` as it is (one ``loadfile`` unit)."""

from perfbench.tests.test_tpch_q3 import *  # noqa: F401,F403
