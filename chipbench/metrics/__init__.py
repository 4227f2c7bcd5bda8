"""Per-layer metric readers, one module per metric, found by the metric's
name.  Each has ``read(art) -> float | None``: ``art`` is what
``harness._artifacts`` gathers from the traced run; ``None`` when there is
nothing to read, so the metric is left out of the result line."""
