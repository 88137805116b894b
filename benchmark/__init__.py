"""The benchmark of tpu-multiraft: see README.md and ../BENCHMARK.json."""
