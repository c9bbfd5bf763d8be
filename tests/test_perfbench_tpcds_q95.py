"""Tier-1 collects ``perfbench/tests/test_tpcds_q95.py`` as it is (one ``loadfile`` unit)."""

from perfbench.tests.test_tpcds_q95 import *  # noqa: F401,F403
