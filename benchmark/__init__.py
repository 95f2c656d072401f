"""The benchmark: one data-driven harness over BENCHMARK.json.

Nothing here is imported by the program. Importing this package starts no
JAX backend: `benchmark.ref` (the plain references) is pure Python and
NumPy, so signing workers and tests can use it without a chip.
"""
