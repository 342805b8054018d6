"""Benchmark harness of the mapping search and the paged service."""
