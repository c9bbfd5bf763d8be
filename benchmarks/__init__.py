"""TPC-DS-shaped benchmark suite (configs 3-5).

The reference publishes no benchmark numbers (SURVEY.md §6); the
driver-set north star is TPC-DS-style relational work: single-chip
joins (config 3) and q5/q23/q64-shaped distributed queries over the
shuffle exchange (configs 4-5). This package provides the synthetic
star-schema generator, the query implementations (single-chip and
mesh-distributed), and a JSON-line runner — the measured baseline the
reference never recorded.
"""
