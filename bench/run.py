"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The last line of standard output is one JSON
object: `correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end
metrics, or with `--trace 1` its per-layer metrics), `device`, with
`--trace 1` a `breakdown`, and last `checks`, each number compared beside
its limit; the last lines of standard error repeat the checks. Without an
accelerator, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# the TPU runtime logs to a fixed path under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the float8 reference in the program's place "
                         "in the check, which must then read not correct "
                         "(the benchmark's runs leave it off)")
    args = ap.parse_args(argv)
    spec = harness.load_spec(args.workload)
    try:
        result, _ = harness.run_cell(spec, args.seed, args.seconds,
                                     bool(args.trace), t_process=T_PROCESS,
                                     control=bool(args.control))
    except harness.NoChip as e:
        print(f"bench: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 2
    reasons = result.pop("reasons")
    if reasons:
        harness.log("not correct: " + "; ".join(reasons))
    for name, c in result["checks"].items():
        harness.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
