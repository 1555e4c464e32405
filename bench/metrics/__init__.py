"""Per-layer metric readers: one ``read(ctx)`` per metric, by name."""
