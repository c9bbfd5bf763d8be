"""The benchmark: the served plan path on the chip, every cell as data.

``python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once. What belongs to one configuration,
one traffic mix or one per-layer metric is a data file found by the name
in ``BENCHMARK.json`` (``configs/``, ``traffic/``, ``layer_metrics/``);
new generator, reference-op or reader kinds are files under ``plugins/``.
From the program the benchmark takes only ``serving.Server`` /
``serving.Client``, the metrics registry's counters and timers, and the
names the device trace prints.
"""
