"""Seeded traffic: request lengths, arrival times and token ids.

One general generator reads a cell's ``traffic`` parameters; a new mix is a
new data file, never new code. Nothing here is the program's.

Every seed gets the same work in another order. Lengths are the ``n``
mid-point quantiles of their distribution (a stratified sample); open-loop
gaps are the quantiles of the exponential distribution (a Poisson process),
so ``n`` requests always span ``n / rate`` seconds. The seed orders them
(``spread_out``): every run of five holds one value from each fifth of the
sorted values, which one and in which order being the seed's. So any few
seconds of a run offer nearly the same work, whatever the seed. With a plain
shuffle the tokens a 50 s window produced swung by 7 % between seeds and by
0.2 % between two runs of one seed (my chip run, PR 25): where the long
answers fell decided how much of them the window saw. Two seeds now differ
in which request meets which, and the spread between runs is the system's.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as the generator means to send it."""
    due_s: Optional[float]      # seconds after the phase starts; None: closed loop
    prompt: np.ndarray          # int32 token ids
    output_tokens: int


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def spread_out(values: np.ndarray, rng: np.random.Generator,
               run: int = 5) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which every ``run``
    consecutive ones hold one from each of ``run`` equal bands of the sorted
    values."""
    bands = [rng.permutation(b) for b in np.array_split(np.sort(values), run)]
    out = []
    for i in range(max(len(b) for b in bands)):
        out.extend(rng.permutation([b[i] for b in bands if i < len(b)]))
    return np.asarray(out, dtype=np.asarray(values).dtype)


def lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole lengths from ``spec``: the distribution's mid-point
    quantiles, clipped to [min, max], spread out in an order from ``rng``."""
    u = _midpoints(n)
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "fixed":
        vals = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", math.inf)
    out = np.clip(np.rint(vals), lo, hi).astype(np.int64)
    return spread_out(out, rng)


def open_gaps(rate_per_s: float, n: int, rng: np.random.Generator,
              cv: float = 1.0) -> np.ndarray:
    """``n`` gaps between arrivals at ``rate_per_s``. ``cv`` 1 is a Poisson
    process (exponential quantiles, spread out). Another coefficient of
    variation draws gamma gaps from ``rng``, left in the order drawn since
    clustering is their point, and scales them to the same total, so that a
    burstier mix offers the same load."""
    if n == 0:
        return np.zeros(0)
    if cv == 1.0:
        return spread_out(-np.log1p(-_midpoints(n)) / rate_per_s, rng)
    shape = 1.0 / (cv * cv)
    gaps = rng.gamma(shape, 1.0 / (shape * rate_per_s), n)
    return gaps * (n / rate_per_s) / gaps.sum()


def _prompts(traffic: Dict, n: int, vocab: int,
             rng: np.random.Generator) -> List[np.ndarray]:
    """Random token ids. With ``prefix: {"groups": g, "tokens": t}`` every
    request starts with its group's ``t`` shared tokens, before its own."""
    own = lengths(traffic["prompt_tokens"], n, rng)
    prefix = traffic.get("prefix")
    heads = None
    if prefix:
        heads = rng.integers(0, vocab, (prefix["groups"], prefix["tokens"]))
        group = rng.integers(0, prefix["groups"], n)
    out = []
    for i in range(n):
        body = rng.integers(0, vocab, int(own[i]))
        if heads is not None:
            body = np.concatenate([heads[group[i]], body])
        out.append(body.astype(np.int32))
    return out


def plan(traffic: Dict, seconds: float, vocab: int,
         rng: np.random.Generator, requests: Optional[int] = None
         ) -> List[Planned]:
    """The requests of one phase of ``seconds``. Open loop: ``rate x
    seconds`` of them with their due times. Closed loop: ``requests`` of
    them (the driver says how many its clients can use), with no times."""
    if traffic["arrivals"] == "open":
        n = int(round(traffic["rate_per_s"] * seconds))
        due = np.cumsum(open_gaps(traffic["rate_per_s"], n, rng,
                                  float(traffic.get("cv", 1.0))))
    elif traffic["arrivals"] == "closed":
        n = int(requests)
        due = [None] * n
    else:
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    prompts = _prompts(traffic, n, vocab, rng)
    outs = lengths(traffic["output_tokens"], n, rng)
    return [Planned(None if due[i] is None else float(due[i]),
                    prompts[i], int(outs[i])) for i in range(n)]


def token_batches(seed: int, vocab: int, sequences: int, tokens: int):
    """Endless training batches of seeded random token ids, made on the
    host one step at a time, as a data loader would hand them over."""
    rng = np.random.default_rng(seed)
    while True:
        yield rng.integers(0, vocab, (sequences, tokens), dtype=np.int32)
