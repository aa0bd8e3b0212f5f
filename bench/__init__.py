"""The benchmark: a harness that runs one cell of BENCHMARK.json on the chip."""
