"""Tier-1 collects ``perfbench/tests/test_zipf.py`` as it is (one ``loadfile`` unit)."""

from perfbench.tests.test_zipf import *  # noqa: F401,F403
