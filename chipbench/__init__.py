"""Chip benchmark of the gDDIM serving stack (see BENCHMARK.json and PERF.md).

One run serves one cell (a model configuration under a traffic mix) on a
TPU through `DiffusionEngine` and `ServeLoop.serve_stream`, times it on
the host clock, reads per-layer metrics from a profiler trace, and checks
the served samples against an independent float32 reference.
"""
