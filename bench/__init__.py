"""On-chip benchmark of the LPQ-ANN served search path (see PERF.md)."""
