"""The chip benchmark: BENCHMARK.json's harness. See README.md."""
