"""The yardstick: timing, trace reduction, FLOP counts, peaks, compile log.

Nothing in this package imports a model or a step builder of the program;
configurations, traffic mixes and per-layer metrics are files of their own
that ``spec.py`` finds by the names in ``BENCHMARK.json``.
"""
