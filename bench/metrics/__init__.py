"""Metric readers, one file per metric: ``read(run) -> float | None``.
A reader that finds nothing to read returns None and the metric is
left out of the result line.  ``base.suffix`` falls back to
``base.py`` (the suffix names the end-to-end metric it moves)."""
