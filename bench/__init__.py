"""The on-chip benchmark: one command runs one cell of ``BENCHMARK.json`` once.

Everything a cell needs is found by name: ``configs/<config>.json`` (the
deployment), ``workloads/<traffic>.json`` (the traffic mix, naming its
generator ``traffic/<generator>.py``), ``reference/<config>.py`` (the plain
reference that decides ``correct``) and ``metrics/<metric>.py`` (one reducer
per metric).  See ``run.py``.
"""
