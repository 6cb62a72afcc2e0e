"""The benchmark's own code: the yardstick that program changes cannot
move.  Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a data file or reader of its own under
``bench/``, found by name from ``BENCHMARK.json``."""
