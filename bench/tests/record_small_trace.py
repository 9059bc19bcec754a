"""Record the small trace `data/small.xplane.pb` that `test_xplane.py`
reduces: a few short programs on one chip inside host annotations named
as the harness names them, with idle gaps between them.

    python3 bench/tests/record_small_trace.py <out_dir>

Run it on the chip; it prints the planes and lines it recorded and the
reduction the test expects.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from bench import xplane

    out_dir = pathlib.Path(out)
    prof = out_dir / "profile"
    shutil.rmtree(prof, ignore_errors=True)

    @jax.jit
    def _decode_step_fn(x, w):
        return jnp.tanh(x @ w) @ w.T

    @jax.jit
    def _prefill_one_fn(x, w):
        return (jnp.tanh(x @ w) @ w.T).sum(0)

    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    xd = jnp.ones((8, 1024), jnp.bfloat16)
    xp = jnp.ones((2048, 1024), jnp.bfloat16)
    jax.block_until_ready((_decode_step_fn(xd, w), _prefill_one_fn(xp, w)))
    jax.profiler.start_trace(str(prof))
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.admit"):
                jax.block_until_ready(_prefill_one_fn(xp, w))
                time.sleep(0.002)
            for _ in range(4):
                with jax.profiler.TraceAnnotation("bench.step"):
                    jax.block_until_ready(_decode_step_fn(xd, w))
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    path = xplane.find_xplane(str(prof))
    shutil.copy(path, out_dir / "small.xplane.pb")
    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("   line", line.name, len(evs),
                  sorted({e.name for e in evs})[:8])
    r = xplane.reduce_trace(xplane.read_events(path))
    print(json.dumps({"window_s": r.window_s, "busy_s": r.busy_s,
                      "program_s": r.program_s,
                      "program_runs": r.program_runs,
                      "top_ops": r.top_ops, "idle": r.idle_by_label,
                      "n_devices": r.n_devices}, indent=1))


if __name__ == "__main__":
    main(sys.argv[1])
