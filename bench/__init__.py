"""Chip benchmark of the streaming DF-P PageRank session (see BENCHMARK.json
and PERF.md)."""
