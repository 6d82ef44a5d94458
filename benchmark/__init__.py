"""The benchmark: one command runs one cell once (``python -m benchmark.run``).

Everything a later PR may not change lives here: traffic generation, the
plain reference, the table of peaks, the operation and byte counts, the
reduction from a device trace to metrics, and the comparison that decides
``correct``. Configurations, traffic mixes, cells and per-layer metrics are
data files found by name; see ``PERF.md``.
"""
