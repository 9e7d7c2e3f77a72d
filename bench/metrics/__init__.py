"""Per-layer metric readers, one module per metric, found by name.

Each module states its ``UNIT``, ``LAYER``, ``MOVES`` and ``SOURCE`` (as
``BENCHMARK.json`` lists them) and ``read(ctx)``, which takes a
``bench.run.RunContext`` and returns the number, or None where the run
has nothing to read.
"""
