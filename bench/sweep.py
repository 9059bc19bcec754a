"""Offered-rate sweep of an open-loop cell, to find the highest rate it
sustains (the cell's mix is then fixed at about four fifths of it).

    python3 bench/sweep.py --workload <name> --seconds <s> --rates <r,r,...>

For each rate, one run of the cell with its mix's `rate_rps` replaced,
without the reference check. One JSON line per rate: requests due, those
with a first token by the window's close, those still queued, and time to
first token for the first and the second half of the window's arrivals;
a rate is sustained where the second half waits no longer than the
first. The benchmark's own runs do not call this.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=12345)
    args = ap.parse_args(argv)
    base = harness.load_spec(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        spec = copy.deepcopy(base)
        spec.mix["arrivals"]["rate_rps"] = rate
        result, run = harness.run_cell(spec, args.seed, args.seconds, False,
                                       t_process=time.perf_counter(),
                                       check=False)
        due = run.due_in_window
        half = run.window0 + args.seconds / 2
        ttft = lambda gs: [g.token_times[0] - g.arrival_s for g in gs
                           if g.token_times and g.token_times[0] <= run.window1]
        first = ttft([g for g in due if g.arrival_s < half])
        second = ttft([g for g in due if g.arrival_s >= half])
        print(json.dumps({
            "rate_rps": rate, "due": len(due),
            "first_token_by_close": sum(
                1 for g in due if g.token_times
                and g.token_times[0] <= run.window1),
            "ttft_p50_first_half_ms": statistics.median(first) * 1e3
            if first else None,
            "ttft_p50_second_half_ms": statistics.median(second) * 1e3
            if second else None,
            "metrics": {k: v["value"] for k, v in result["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
