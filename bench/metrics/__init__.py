"""Per-layer metric readers: ``bench/metrics/<metric>.py`` each define
``UNIT`` and ``read(rec) -> float | None`` (None: nothing to read)."""
