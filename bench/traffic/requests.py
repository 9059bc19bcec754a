"""Generator of the `requests` traffic kind: prompts and output budgets
drawn from clipped lognormals, arriving as a backlog or an open loop.

The mix file gives the distributions and the schedule; the run's seed
gives the token ids alone. Every seed gets the same requests at the same
times, so runs of different seeds differ by the noise of the run, not by
their work. The requests come in blocks of `block`, each holding the
same stratified draw, the (i + 1/2)/block quantiles of each
distribution, in an order shuffled per block by the mix's own
`schedule_seed`. Open-loop gaps are independent exponentials drawn from
the same fixed stream: one sample path of a Poisson process, with its
bursts, the same in every run.

Mix keys:
  arrivals  {"process": "backlog", "count": n}: n requests all due at
            the window's start; or {"process": "poisson", "rate_rps": r}:
            independent exponential gaps of mean 1/r, as many as the
            window holds.
  schedule_seed  seeds the order of each block and the gaps.
  block     requests per stratified block.
  prompt    {"median", "sigma", "min", "max", "grid"}: lognormal lengths
            clipped to [min, max], then rounded to the grid point nearest
            in log space (the engine compiles one prefill per length).
  output    {"median", "sigma", "min", "max"}: token budgets, clipped and
            rounded to whole tokens.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request of a mix: due `due_s` seconds after the window opens."""
    due_s: float
    prompt: np.ndarray          # (n,) int32 token ids
    max_new_tokens: int


def _lognormal_quantiles(spec: dict, n: int) -> list[float]:
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    nd = NormalDist()
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
        out.append(min(max(x, spec["min"]), spec["max"]))
    return out


def _to_grid(x: float, grid: list[int]) -> int:
    return min(grid, key=lambda g: abs(math.log(g) - math.log(x)))


def block_sizes(mix: dict) -> list[tuple[int, int]]:
    """The (prompt length, output budget) pairs of one block, in their
    unshuffled order. Prompt quantile i is paired with output quantile
    (5 i + 3) mod block, so that long prompts do not go with long
    outputs only."""
    b = mix["block"]
    grid = sorted(mix["prompt"]["grid"])
    prompts = [_to_grid(x, grid) for x in _lognormal_quantiles(mix["prompt"], b)]
    outs = [int(round(x)) for x in _lognormal_quantiles(mix["output"], b)]
    step = 5 if math.gcd(5, b) == 1 else 1
    return [(prompts[i], outs[(step * i + 3) % b]) for i in range(b)]


def prompt_lengths(mix: dict) -> list[int]:
    """Every prompt length the mix can send: the grid points it uses."""
    return sorted({p for p, _ in block_sizes(mix)})


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list[Arrival]:
    """The requests of one run, ordered by due time. Sizes and due times
    depend on the mix alone; `seed` draws the prompts' token ids."""
    sched = np.random.default_rng(int(mix["schedule_seed"]))
    tokens = np.random.default_rng([seed, 1])
    pairs = block_sizes(mix)
    b = len(pairs)
    arr = mix["arrivals"]
    if arr["process"] == "backlog":
        n, mean = int(arr["count"]), None
    elif arr["process"] == "poisson":
        n, mean = None, 1.0 / float(arr["rate_rps"])
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    out: list[Arrival] = []
    t = 0.0
    while n is None or len(out) < n:
        order = sched.permutation(b)
        gaps = sched.exponential(1.0, b)
        for j in range(b if n is None else min(b, n - len(out))):
            plen, out_len = pairs[order[j]]
            if mean is not None:
                t += mean * gaps[j]
                if t >= seconds:
                    return out
            prompt = tokens.integers(0, vocab, size=plen, dtype=np.int32)
            out.append(Arrival(t, prompt, out_len))
    return out
