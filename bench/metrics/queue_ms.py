"""Median over the requests due in the window of the program's `queue`
span: from `Gateway.submit` to the admission round that pops it."""
import statistics


def read(run):
    due = {g.rid for g in run.due_in_window}
    waits = [ev.dur_s for ev in run.spans or ()
             if ev.kind == "queue" and ev.attrs.get("rid") in due
             and "reason" not in ev.attrs]
    return statistics.median(waits) * 1e3 if waits else None
