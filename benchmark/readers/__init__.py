"""One small reader per per-layer metric: ``read(ctx, **args)`` takes the
metric from the run's spans, counters or reduced trace and returns a
number, or ``None`` where there is nothing to read (the metric is then
left out of the line; a share of a roofline or of a peak is never 0)."""
