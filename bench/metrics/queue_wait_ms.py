"""Median over requests due in the window of time to first token less the
request's own `prefill_step` span: the wait in the gateway's queue and
behind other work."""
import statistics


def read(run):
    prefill = {ev.name: ev.dur_s for ev in run.spans or ()
               if ev.kind == "prefill_step"}
    waits = [g.token_times[0] - g.arrival_s - prefill[f"req{g.rid}"]
             for g in run.due_in_window
             if g.token_times and f"req{g.rid}" in prefill]
    return statistics.median(waits) * 1e3 if waits else None
