"""One reader per per-layer metric, found by the metric's name.

Each module ``<metric>.py`` defines ``read(rec) -> float | None`` over the
run record the harness builds (see ``bench/harness.py::run_record``). A
reader that finds nothing to read returns None and the metric is left out
of the result line.
"""
