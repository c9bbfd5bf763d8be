"""Tier-1 collects ``perfbench/tests/test_tpch_q18.py`` as it is (one ``loadfile`` unit)."""

from perfbench.tests.test_tpch_q18 import *  # noqa: F401,F403
