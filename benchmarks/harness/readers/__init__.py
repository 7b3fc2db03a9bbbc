"""One module per reader of a per-layer metric. A metric's data file
(``benchmarks/layer_metrics/<name>.json``) names its reader and the reader's
parameters; ``read(record, **params)`` returns the value, or ``None`` where
there is nothing to read (the harness then leaves the metric out)."""
