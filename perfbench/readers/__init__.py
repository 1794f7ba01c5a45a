"""One small reader per kind of per-layer reading. `read(observed, **args)`
takes the number from the run's spans, counters or trace; a reader that finds
nothing to read returns None and the metric is left out of the line."""
