"""Benchmark library: cells, workloads, reference, comparison, traces."""
