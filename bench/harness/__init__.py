"""The benchmark's harness: what turns a cell of BENCHMARK.json into a run."""
