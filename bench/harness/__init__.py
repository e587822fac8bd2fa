"""Shared code of the chip benchmark: device checks, weights, the plain
float32 reference, the trace reduction, FLOP and byte counts, and the
comparison that decides ``correct``.  Cells, traffic mixes and per-layer
metrics live in files of their own beside this package (``bench/README.md``).
"""
