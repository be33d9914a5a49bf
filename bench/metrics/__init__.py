"""Per-layer metric readers, one file each: ``bench/metrics/<name>.py``,
with ``.`` and ``-`` of the metric's name written ``_``.  Each exposes
``read(ctx) -> float | None``; ``None`` means nothing to read, and the
metric is then left out of the result."""
