"""Chip benchmark: one served cell per run, driven by ``BENCHMARK.json``.

A cell is a model configuration (``configs/<name>.json``) under a traffic
mix (``traffic/<name>.json``); each per-layer metric is a reader of its
own (``metrics/<name>.py``).  ``run.py`` finds all three by the names in
``BENCHMARK.json``, so a new cell or metric is a new file, not an edit.
"""
