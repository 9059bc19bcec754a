"""Engine `prefill_step` span time per thousand prompt tokens."""


def read(run):
    spans = run.spans_in_window("prefill_step")
    tokens = sum(a["prompt_len"] for _, _, a in spans)
    if not tokens:
        return None
    return sum(e - s for s, e, _ in spans) / tokens * 1e6
