"""The chip benchmark: one command (``bench/run.py``), the cell table in
``BENCHMARK.json`` at the checkout's root, and the yardstick it measures
with (traffic generation, weights from the seed, the plain reference,
work counts, device peaks and the reduction of profiler traces).

A configuration is ``configs/<name>.json``, a traffic mix
``traffic/<name>.json`` and a per-layer metric ``metrics/<name>.py``; the
harness finds each by the name ``BENCHMARK.json`` gives it.
"""
