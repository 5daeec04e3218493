"""The benchmark: one command that runs one cell of ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own that the harness finds by name:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.py`` and ``work/<kernel>.py``.
"""
