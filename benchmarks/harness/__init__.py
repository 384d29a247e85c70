"""The benchmark's own yardstick: nothing here imports bench.py,
chip_smoke.py, tools/ or tests/. See benchmarks/README.md."""
