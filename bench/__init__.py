"""DASO training benchmark (`python3 bench/run.py --workload <cell> ...`).

The yardstick of the repository: traffic generation, seeded weights, the
plain reference that decides `correct`, the reduction from device traces
to metrics, the peak table and the FLOP count. What a cell, a
configuration, a traffic mix or a per-layer metric is lives in its own
file, found by the name `BENCHMARK.json` gives it."""
