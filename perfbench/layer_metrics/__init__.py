"""Per-layer metric readers, one file a metric (see reading.py)."""
