"""Reader kinds: one module per kind, named by a metric file's
``reader`` key.  Each exposes ``read(record, args) -> float | None``
over a :class:`bench.harness.RunRecord`.  A reader that finds nothing to
read returns ``None`` and the metric is left out of the result; a share
of a roofline is never reported as 0 for want of a reading.
"""
