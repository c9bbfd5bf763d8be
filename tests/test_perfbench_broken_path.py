"""Tier-1 collects ``perfbench/tests/test_broken_path.py`` as it is (one ``loadfile`` unit)."""

from perfbench.tests.test_broken_path import *  # noqa: F401,F403
