"""Chip benchmark of the pub/sub stream engine.

One command runs one cell of ``BENCHMARK.json`` once (``bench/run.py``).
Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``  names a builder kind (``bench/builders``)
* ``bench/traffic/<mix>.json``     names a generator kind (``bench/traffic``)
* ``bench/metrics/<metric>.json``  names a reader kind (``bench/readers``)

Kinds are modules, imported by name, so a new cell, mix or metric is
added by adding files.
"""
