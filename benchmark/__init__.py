"""The repo's benchmark: harness, configurations, traffic mixes, reference.

See README.md beside this file and BENCHMARK.json at the repo root.
"""
