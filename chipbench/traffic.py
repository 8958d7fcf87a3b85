"""One generator for every traffic mix, read from its data file.

A mix (``mixes/<name>.json``) states its loop, its lengths and, for an
open loop, how arrivals are spread; the cell file states the rate. The
lengths and the gaps between arrivals are the quantiles of the stated
distributions at evenly spaced probabilities, put in an order that the
mix's ``order_seed`` fixes. So every run of a cell sends the same
requests at the same times, and ``--seed`` draws only their tokens (and
the weights): a window holds a dozen or so chat requests, and an order
drawn per run changed which of them fell in it enough to move its tail
and its rate by a third from seed to seed.

A closed loop is caught in its steady state: each client's request has
already run for a while when the window opens, so its ``context`` (the
prompt and the output made so far, tokens drawn from the seed) is built
in set-up and the window decodes on from there.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request as the generator makes it."""

    uid: int
    prompt: np.ndarray  # (P,) int32
    max_new: int
    due: Optional[float] = None  # seconds after the window opens (open loop)


def quantiles(n: int, dist: dict) -> list[int]:
    """n whole lengths at the probabilities (i + 1/2) / n of ``dist``."""
    lo, hi = dist["lo"], dist["hi"]
    out = []
    for i in range(n):
        q = (i + 0.5) / n
        if dist["dist"] == "uniform":
            v = lo + q * (hi - lo + 1) - 0.5
        elif dist["dist"] == "lognormal":
            v = dist["median"] * math.exp(dist["sigma"]
                                          * NormalDist().inv_cdf(q))
        else:
            raise ValueError(f"unknown length distribution {dist['dist']!r}")
        out.append(int(min(max(round(v), lo), hi)))
    return out


def gaps(n: int, seconds: float, arrivals: str) -> np.ndarray:
    """n gaps between arrivals summing to ``seconds``: exponential
    quantiles (a Poisson process) or equal steps."""
    if arrivals == "poisson":
        q = (np.arange(n) + 0.5) / n
        g = -np.log1p(-q)
    elif arrivals == "uniform":
        g = np.ones(n)
    else:
        raise ValueError(f"unknown arrivals {arrivals!r}")
    return g * (seconds / g.sum())


def generate(mix: dict, load: dict, *, seed: int, seconds: float,
             slots: int, max_len: int, vocab: int) -> list[Spec]:
    """The requests of one run.

    ``closed``: one client per slot, each with one request whose tokens
    before the window are ``context`` long and whose output budget fills
    the rest of ``max_len`` (``output`` is ``"fill"``) or is drawn like
    the open loop's. ``open``: ``rate_per_s * seconds`` requests with
    ``prompt`` lengths, due at the cumulative gaps (the first at 0).
    """
    rng = np.random.default_rng(seed % (1 << 64))
    order = np.random.default_rng(mix["order_seed"])
    if mix["loop"] == "closed":
        n, lengths = slots, mix["context"]
    elif mix["loop"] == "open":
        n, lengths = max(1, round(load["rate_per_s"] * seconds)), mix["prompt"]
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    plens = order.permutation(quantiles(n, lengths))
    if mix["output"] == "fill":
        outs = [max_len - int(p) for p in plens]
    else:
        outs = order.permutation(quantiles(n, mix["output"]))
    due = [None] * n
    if mix["loop"] == "open":
        g = order.permutation(gaps(n, seconds, mix["arrivals"]))
        due = np.concatenate([[0.0], np.cumsum(g)[:-1]]).tolist()
    reqs = []
    for i in range(n):
        p, o = int(plens[i]), int(outs[i])
        if p + o > max_len or o < 1:
            raise ValueError(f"mix asks {p} + {o} tokens; max_len is "
                             f"{max_len}")
        reqs.append(Spec(uid=i, prompt=rng.integers(0, vocab, p,
                                                    dtype=np.int32),
                         max_new=o, due=due[i]))
    return reqs


def prefill_buckets(specs: list[Spec]) -> list[int]:
    """The engine's prefill lengths (held-back last token, power-of-two
    buckets) these requests reach, one per bucket."""
    out = set()
    for s in specs:
        n = len(s.prompt) - 1
        if n > 0:
            out.add(1 << (n - 1).bit_length())
    return sorted(out)
