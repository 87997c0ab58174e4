"""Per-layer metrics, one reader per file: ``read(run)`` takes the
harness's view of one run (``bench.harness.RunView``) and returns the
metric's value, or None when the run holds nothing to read it from."""
