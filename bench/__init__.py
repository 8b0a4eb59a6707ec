"""The chip benchmark: one cell of ``BENCHMARK.json`` per run of ``run.py``.

Everything a cell needs is found by name: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``drivers/<driver>.py`` (named by the
configuration), ``reference/<reference>.py`` and one reader
``metrics/<metric>.py`` per per-layer metric.
"""
