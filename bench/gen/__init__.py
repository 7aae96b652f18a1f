"""Seeded graph and update-stream generators of the benchmark (copies kept
with the yardstick, so that a change to the program cannot move them)."""
