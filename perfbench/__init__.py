"""The benchmark of the batched Monte-Carlo engine (see PERF.md)."""
