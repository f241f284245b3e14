"""The one traffic generator: turns a mix file (`traffic/<name>.json`) and a
seed into requests.

Every seed gets the same set of sizes and arrival gaps: the sizes are the
quantiles of the mix's distributions at (i + 1/2)/n, and the seed shuffles
them and draws the prompt tokens. So runs with different seeds do the same
amount of work, and differ only in its order and content. A mix that gives
`order_seed` fixes the order too (a replayed schedule): an open-loop tail over
a few dozen requests swings with the order of its long prompts and gaps, and
the seed then draws only the content.

Mix keys:
  loop          "closed" (each of `clients` clients sends its next request
                when the last one finished) or "open" (arrivals on a schedule
                at `rate_per_s`, in bursts of `burst`, whatever the server does)
  prompt_len    {"ladder": [...]} lengths drawn evenly from the ladder, or
                {"lognormal": {"median", "sigma"}, "ladder": [...]} drawn from
                the lognormal and rounded up onto the ladder (clipped to it)
  new_tokens    {"fixed": n} or {"pareto": {"min", "max", "alpha"}}: a
                Pareto tail cut at max
  n_requests    closed loop: size of the request set, cycled when used up
  serve_mode    "offload" or "resident": the server path the mix drives
  slots, page_size   decode slots and tokens per KV page
  order_seed    optional: the order of sizes and gaps, whatever the run's seed
The open-loop arrival generator follows `_arrivals` of the repository's
`benchmarks/load_harness.py` (Poisson bursts sharing one instant).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class Plan:
    prompt_lens: List[int]
    new_tokens: List[int]
    due_s: Optional[List[float]]       # open loop: offsets from window start


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream `stream` of `seed` (any size of integer)."""
    return np.random.default_rng([int(seed) % 2**63, stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _onto_ladder(x: np.ndarray, ladder: Sequence[int]) -> np.ndarray:
    lad = np.asarray(sorted(ladder))
    idx = np.searchsorted(lad, np.ceil(x), side="left")
    return lad[np.clip(idx, 0, len(lad) - 1)]


def prompt_lengths(spec: dict, n: int) -> np.ndarray:
    ladder = spec["ladder"]
    if "lognormal" in spec:
        from statistics import NormalDist
        ln = spec["lognormal"]
        z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
        return _onto_ladder(ln["median"] * np.exp(ln["sigma"] * z), ladder)
    lad = sorted(ladder)
    return np.asarray([lad[i % len(lad)] for i in range(n)])


def new_token_counts(spec: dict, n: int) -> np.ndarray:
    if "fixed" in spec:
        return np.full(n, int(spec["fixed"]))
    p = spec["pareto"]
    x = p["min"] * (1.0 - _quantiles(n)) ** (-1.0 / p["alpha"])
    return np.minimum(np.floor(x), p["max"]).astype(np.int64)


def arrival_offsets(n: int, rate: float, burst: int,
                    rng: np.random.Generator) -> np.ndarray:
    """Open-loop arrivals: bursts of `burst` requests share one instant; the
    gaps between bursts are the Exp(burst/rate) quantiles in a seeded order,
    so the mean rate is `rate` and every seed has the same gaps."""
    n_bursts = -(-n // burst)
    gaps = -np.log1p(-_quantiles(n_bursts)) * burst / rate
    rng.shuffle(gaps)
    return np.repeat(np.cumsum(gaps) - gaps[0], burst)[:n]


def plan(mix: dict, seed: int, seconds: float) -> Plan:
    rng = rng_for(mix.get("order_seed", seed), 1)
    if mix["loop"] == "closed":
        n = int(mix["n_requests"])
        due = None
    elif mix["loop"] == "open":
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
        due = arrival_offsets(n, float(mix["rate_per_s"]),
                              int(mix.get("burst", 1)), rng).tolist()
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    lens = prompt_lengths(mix["prompt_len"], n)
    outs = new_token_counts(mix["new_tokens"], n)
    order = rng.permutation(n)
    return Plan([int(x) for x in lens[order]], [int(x) for x in outs[order]],
                due)


def prompt_tokens(seed: int, index: int, length: int,
                  vocab: int) -> np.ndarray:
    """Tokens of request `index`: its own stream, so a request's content does
    not depend on how many were drawn before it."""
    return np.random.default_rng([int(seed) % 2**63, 2, index]).integers(
        0, vocab, length).astype(np.int32)


def ladder(mix: dict) -> List[int]:
    return sorted(mix["prompt_len"]["ladder"])


def max_new_tokens(mix: dict) -> int:
    spec = mix["new_tokens"]
    return int(spec["fixed"]) if "fixed" in spec else int(spec["pareto"]["max"])


def pages_needed(mix: dict, page_size: int, concurrent: int) -> int:
    """Pages that let `concurrent` requests of the longest kind be admitted
    under strict (worst-case) admission."""
    per = math.ceil((max(ladder(mix)) + max_new_tokens(mix)) / page_size)
    return per * concurrent
