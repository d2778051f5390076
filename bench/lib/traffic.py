"""The one traffic generator: a mix's data file in, an open-loop schedule out.

A mix file (``bench/traffic/<mix>.json``) states distributions, never
draws. For a window of ``seconds`` at ``rate_per_s`` the schedule holds
exactly ``N = round(rate_per_s * seconds)`` requests:

* due times: the gaps between arrivals are the N quantile points of the
  exponential distribution, in one fixed order, scaled to the window;
* lengths: the N quantile points ``(i + 0.5) / N`` of each stated
  distribution, in one fixed order;
* shared prompts: a fixed number of prompts whose lengths are the
  quantile points of their distribution, each used a fixed number of
  times (Zipf popularity rounded by largest remainder), in one fixed
  order.

The order is drawn from ``ORDER_SEED``, not from the run's seed, so
every run of a mix offers the very same schedule: the same due times,
lengths and hit/miss sequence. The run's seed draws only the words
(and, in the harness, the weights). Near a cell's knee one engine-thread
stall queues every later arrival, so a seeded order moved a whole
window's latencies from run to run; a fixed one keeps that out.
A token is one word: ``w<n>`` encodes to exactly one id, and the chat
template adds ``[role]`` (3 tokens) per message and BOS once.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

TEMPLATE_TOKENS = 3          # "[", role, "]" before each message
BOS_TOKENS = 1
ORDER_SEED = 20261018        # the one order of every mix's schedule


@dataclass
class Request:
    due_s: float
    messages: Tuple[Tuple[str, str], ...]
    max_tokens: int
    prompt_id: Optional[int] = None       # shared prompt index, if any
    tenant: str = "bench"

    def body(self, model: str) -> dict:
        return {"model": model, "stream": True, "user": self.tenant,
                "max_tokens": self.max_tokens,
                "messages": [{"role": r, "content": c}
                             for r, c in self.messages]}


def quantiles(dist: dict, n: int) -> List[int]:
    """The n quantile points (i + 0.5) / n of a stated distribution,
    rounded to whole tokens, in ascending order."""
    lo, hi = float(dist["lo"]), float(dist["hi"])
    out = []
    for i in range(n):
        q = (i + 0.5) / n
        if dist["dist"] == "uniform":
            v = lo + q * (hi - lo)
        elif dist["dist"] == "loguniform":
            v = lo * (hi / lo) ** q
        else:
            raise ValueError(f"unknown distribution {dist['dist']!r}")
        out.append(int(round(v)))
    return out


def zipf_counts(n_items: int, a: float, total: int) -> List[int]:
    """Counts per popularity rank, P(rank r) ~ 1/(r+1)^a, rounded by
    largest remainder so they sum to ``total``."""
    w = [1.0 / (r + 1) ** a for r in range(n_items)]
    s = sum(w)
    exact = [total * x / s for x in w]
    counts = [int(math.floor(x)) for x in exact]
    rest = sorted(range(n_items), key=lambda r: (-(exact[r] - counts[r]), r))
    for r in rest[:total - sum(counts)]:
        counts[r] += 1
    return counts


def _words(rng: np.random.Generator, n: int) -> str:
    return " ".join(f"w{int(x)}" for x in rng.integers(0, 10_000, n))


def longest(mix: dict) -> int:
    """The most tokens one request of the mix holds: prompt and answer."""
    sp = mix.get("shared_prompts")
    return ((sp["tokens"]["hi"] if sp else 0) + mix["user_tokens"]["hi"]
            + mix["output_tokens"]["hi"])


def count(rate_per_s: float, seconds: float) -> int:
    return int(round(rate_per_s * seconds))


def shared_prompt_lengths(mix: dict) -> List[int]:
    sp = mix.get("shared_prompts")
    return quantiles(sp["tokens"], sp["count"]) if sp else []


def shared_prompts(mix: dict, seed: int) -> List[str]:
    """The shared prompt texts of this seed, from a stream of their own
    (so they do not depend on the window's request count)."""
    sp = mix.get("shared_prompts")
    if not sp:
        return []
    rng = np.random.default_rng([seed, 1])
    return [f"doc {j}: " + _words(rng, L - BOS_TOKENS - TEMPLATE_TOKENS - 3)
            for j, L in enumerate(shared_prompt_lengths(mix))]


def arrivals(rng: np.random.Generator, n: int,
             seconds: float) -> np.ndarray:
    """n due times in (0, seconds), ascending: the exponential
    distribution's n quantile gaps in the order ``rng`` draws."""
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))              # Exp(1) quantiles
    # arrival i follows arrival i-1 by gap i; one mean gap is left after
    # the last, so the window holds them all
    return seconds * np.cumsum(gaps) / (gaps.sum() + gaps.mean()) \
        if n else gaps


def schedule(mix: dict, seed: int, rate_per_s: float,
             seconds: float) -> List[Request]:
    """The window's requests, sorted by due time."""
    rng = np.random.default_rng(seed)                  # the words
    orng = np.random.default_rng(ORDER_SEED)           # the order
    n = count(rate_per_s, seconds)
    due = arrivals(orng, n, seconds)
    user = orng.permutation(quantiles(mix["user_tokens"], n))
    out_toks = orng.permutation(quantiles(mix["output_tokens"], n))
    sp = mix.get("shared_prompts")
    prompts = shared_prompts(mix, seed)
    order: list = [None] * n
    if sp:
        by_rank = zipf_counts(sp["count"], sp["zipf_a"], n)
        counts = [by_rank[r] for r in sp["rank_of_prompt"]]
        order = list(orng.permutation(
            np.repeat(np.arange(sp["count"]), counts)))
    reqs = []
    for i in range(n):
        msgs = []
        pid = None if order[i] is None else int(order[i])
        if pid is not None:
            msgs.append((sp.get("role", "system"), prompts[pid]))
        # user-turn tokens include the template (and BOS when first)
        u_words = int(user[i]) - TEMPLATE_TOKENS \
            - (0 if msgs else BOS_TOKENS)
        msgs.append(("user", _words(rng, u_words)))
        reqs.append(Request(due_s=float(due[i]), messages=tuple(msgs),
                            max_tokens=int(out_toks[i]), prompt_id=pid,
                            tenant=f"t{i % 4}"))
    return reqs
