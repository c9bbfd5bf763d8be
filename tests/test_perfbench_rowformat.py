"""Tier-1 collects ``perfbench/tests/test_rowformat.py`` as it is (one ``loadfile`` unit)."""

from perfbench.tests.test_rowformat import *  # noqa: F401,F403
