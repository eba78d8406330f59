"""On-chip benchmark of the HPCG solves: cells, traffic, readers, reference.

Everything here is found by name from ``BENCHMARK.json`` at the checkout
root: a configuration is ``bench/configs/<config>.json``, a traffic mix is
``bench/traffic/<traffic>.json`` and every metric is read by
``bench/metrics/<metric>.py``. Run a cell with ``python3 bench/run.py``.
"""
