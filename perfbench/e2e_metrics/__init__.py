"""End-to-end metric readers, one file a metric (see reading.py)."""
