"""Tier-1 collects ``perfbench/tests/test_contract.py`` as it is (one ``loadfile`` unit)."""

from perfbench.tests.test_contract import *  # noqa: F401,F403
