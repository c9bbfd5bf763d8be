"""Tier-1 collects ``perfbench/tests/test_rehearse.py`` as it is (one ``loadfile`` unit)."""

from perfbench.tests.test_rehearse import *  # noqa: F401,F403
