"""Chip benchmark of the plan engine, driven by data.

``BENCHMARK.json`` at the root of the checkout names the cells. Each cell
pairs a configuration (``configs/<name>.json``) with a traffic mix
(``traffic/<name>.json``); the configuration names the driver that runs
it (``drivers/<name>.py``), and each metric is a reader of its own
(``metrics/<name>.py``). A new cell, mix, driver or metric is a new file
and a new entry, never an edit of an existing file.
"""
