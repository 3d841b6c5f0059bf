"""Benchmark harness: cells, traffic, readers and the plain reference."""
