"""Shared parts of the benchmark: written once, read by every cell."""
