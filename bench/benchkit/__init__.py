"""The chip benchmark's harness: everything a cell shares.

A cell is found by name: ``registry`` reads ``BENCHMARK.json`` and loads the
configuration, the traffic mix, the driver, the plain reference, the metric
readers and the peak table from files of their own under ``bench/``.
"""
