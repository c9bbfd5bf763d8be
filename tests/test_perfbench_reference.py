"""Tier-1 collects ``perfbench/tests/test_reference.py`` as it is (one ``loadfile`` unit)."""

from perfbench.tests.test_reference import *  # noqa: F401,F403
