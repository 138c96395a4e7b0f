"""One reader per per-layer metric: ``read(ctx)`` returns the value, or None where its window holds nothing to read."""
