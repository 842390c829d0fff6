"""One reader per metric, in ``<metric name>.py``: ``read(run)`` returns the
metric's value from a finished run (``bench.harness.Run``), or None where
the run has nothing to read it from."""
