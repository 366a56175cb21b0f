"""One small reader per kind of per-layer metric: ``read(ctx, **args)`` takes
the number from the window's spans and counters (``ctx['measured']``) or the
reduced trace (``ctx['reduced']``, ``ctx['trace']``) and returns it, or
``None`` where there is nothing to read (the metric is then left out)."""
