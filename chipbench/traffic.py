"""The one traffic generator.  A mix is a data file (``traffic/<name>.json``,
merged with the cell's own ``workloads/<cell>.json``); this module turns it
and a seed into requests.

Every seed gets the same multiset of sizes and arrival gaps, in another
order: lengths are the lognormal's quantiles at evenly spaced levels
(clipped to the mix's range), gaps the exponential's, and the seed only
permutes them and draws the prompt token ids.  So two seeds ask for the
same work, and the spread between seeds is the system's, not the dice's.

Arrival kinds:
  ``backlog``  every request is due at t=0.  Sizes come in blocks of
               ``block`` requests, each block the same multiset in its own
               order, so the batch the engine starts with is the same
               multiset for every seed.
  ``poisson``  open loop at ``rate_per_s``: gaps are the exponential's
               quantiles, permuted; only requests due inside the window
               are made.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

__all__ = ["Req", "lognormal_sizes", "exponential_gaps", "make_requests",
           "seed_rng"]


@dataclasses.dataclass
class Req:
    rid: int
    arrival: float          # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new: int


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one purpose (``stream``) of one seed; any
    non-negative whole number is a valid seed."""
    return np.random.default_rng([int(seed), int(stream)])


def lognormal_sizes(n: int, dist: dict) -> np.ndarray:
    """``n`` sizes at the lognormal's quantiles (i + 0.5) / n, median
    ``dist['median']``, log-sd ``dist['sigma']``, clipped to
    [``min``, ``max``]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` gaps at the exponential's quantiles (i + 0.5) / n."""
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate


def make_requests(mix: dict, seed: int, vocab: int,
                  seconds: float) -> list[Req]:
    arr = mix["arrivals"]
    order = seed_rng(seed, 1)
    tok_rng = seed_rng(seed, 2)
    if arr["kind"] == "backlog":
        block, n = int(arr["block"]), int(arr["requests"])
        if n % block:
            raise ValueError(f"backlog of {n} requests is not whole blocks "
                             f"of {block}")
        base_p = lognormal_sizes(block, mix["prompt"])
        base_o = lognormal_sizes(block, mix["output"])
        plens = np.concatenate([order.permutation(base_p)
                                for _ in range(n // block)])
        outs = np.concatenate([order.permutation(base_o)
                               for _ in range(n // block)])
        arrivals = np.zeros(n)
    elif arr["kind"] == "poisson":
        rate = float(arr["rate_per_s"])
        n = max(int(math.floor(rate * seconds)), 1)
        # every gap is used, so the last arrival (their sum) is the same
        # for every seed
        arrivals = np.cumsum(order.permutation(exponential_gaps(n, rate)))
        keep = arrivals < seconds
        n = int(keep.sum())
        arrivals = arrivals[keep]
        plens = order.permutation(lognormal_sizes(n, mix["prompt"]))
        outs = order.permutation(lognormal_sizes(n, mix["output"]))
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    return [Req(rid=i, arrival=float(arrivals[i]),
                prompt=tok_rng.integers(0, vocab, int(plens[i]),
                                        dtype=np.int32),
                max_new=int(outs[i]))
            for i in range(len(plens))]

