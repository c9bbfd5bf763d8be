"""Tier-1 collects ``perfbench/tests/test_layer_sources.py`` as it is (one ``loadfile`` unit)."""

from perfbench.tests.test_layer_sources import *  # noqa: F401,F403
