"""Shared machinery of the benchmark; nothing here names a cell, a
configuration or a metric (those are data files found by name)."""
