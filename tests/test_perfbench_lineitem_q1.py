"""Tier-1 collects ``perfbench/tests/test_lineitem_q1.py`` as it is (one ``loadfile`` unit)."""

from perfbench.tests.test_lineitem_q1 import *  # noqa: F401,F403
