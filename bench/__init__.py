"""The chip benchmark: one data-driven harness (``run.py``), with its
configurations, cells, traffic mixes, metric readers, peak table, FLOP
counts, trace reduction and plain references in files of their own."""
