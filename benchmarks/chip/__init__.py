"""Chip benchmark of the photon-transport engine.

``run.py`` is the command; ``BENCHMARK.json`` at the checkout's root
names its configurations, cells and metrics.  Everything that belongs to
one configuration, cell, entry point or per-layer metric is a file of
its own under ``configs/``, ``workloads/``, ``entries/`` and
``metrics/``, found by name (``harness.py``).
"""
