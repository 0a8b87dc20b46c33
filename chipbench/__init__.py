"""Chip benchmark of the secure job service: cells, metrics and references.

`run.py` is the entry point. Everything a cell needs is found by name:
`BENCHMARK.json` at the checkout's root lists the cells, and each names a
configuration (`configs/<config>.json`), a traffic mix
(`traffic/<traffic>.json`) and the chips it needs. A configuration names
its job kind (`jobs/<job>.py`: data generator, submit call, plain
reference, comparison); every metric is a reader of its own
(`metrics/<metric>.py`). `peaks.json` holds the chips' published peaks.
"""
