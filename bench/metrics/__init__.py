"""Per-layer metric readers: ``bench/metrics/<metric>.py`` defines
``read(obs) -> float | None`` over ``trace_reduce.Observations``. A reader
that finds nothing to read returns None and the metric is left out."""
