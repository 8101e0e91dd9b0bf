"""Benchmark of implicitglobalgrid_tpu on the chip (see BENCHMARK.json, PERF.md)."""
