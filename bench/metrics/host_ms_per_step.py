"""Window time outside the engine's prefill and decode spans and outside
the waits for the next arrival, per decode step: the gateway's and the
planner's host work between engine calls."""


def read(run):
    if run.spans is None or run.steps == 0:
        return None
    inside = sum(e - s for kind in ("prefill_step", "decode_step")
                 for s, e, _ in run.spans_in_window(kind))
    return (run.window_s - run.wait_s - inside) / run.steps * 1e3
