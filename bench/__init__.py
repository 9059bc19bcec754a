"""The on-chip benchmark: seeded traffic through the serving gateway,
checked against a plain reference.

`python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>`
from the root of a checkout runs one cell of `BENCHMARK.json`. Everything
that belongs to one configuration, traffic mix or per-layer metric is a
file of its own, found by name: `configs/<config>.json`,
`traffic/<mix>.json` (read by the generator `traffic/<kind>.py`),
`metrics/<metric>.py`, and `reference/<family>.py` with its counts in
`reference/<family>_counts.py`.
"""
