"""The on-chip benchmark: harness, traffic, reference, metric readers."""
