"""The one traffic generator. It reads a traffic mix, a data file under
``chipbench/traffic/``, and makes a run's requests from ``--seed``.

Every seed gets the same request sizes and the same gaps between
arrivals, in the same order: sizes are the quantiles of the mix's
distributions at evenly spaced probabilities, and gaps those of an
exponential, each shuffled by a fixed permutation of its own. The seed
draws what the requests say (their tokens; the run's weights and corpus
come from it too). So runs with different seeds carry the same work on
the same schedule, and a tail such as the 95th percentile of time to first
token does not swing with which sizes happen to arrive together.

A mix has ``arrival`` either ``{"kind": "poisson", "rate_per_s": r}``, an
open loop whose gaps are exponential quantiles at mean ``1/r``, or
``{"kind": "closed", "clients": c}``, where each client sends its next
request as soon as the previous one has finished.
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

from chipbench import corpus as corpus_lib

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


@dataclass
class Spec:
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int


def lognormal_quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at probabilities (i + 1/2) / n of a lognormal with the
    given median and sigma, rounded and clipped to [min, max]."""
    nd = NormalDist()
    u = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(x)) for x in u])
    v = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(v, dist["min"], dist["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` gaps at probabilities (i + 1/2) / n of an exponential, scaled so
    that they sum to exactly ``n / rate``."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (n / rate) / g.sum()


def requests(mix: dict, n: int, seed: int, vocab_size: int,
             stream: int) -> List[Spec]:
    """``n`` requests of the mix. ``stream`` tells apart the sets one run
    draws (warm-up, window) so that they share no tokens."""
    order = np.random.default_rng([0x5E, stream])
    p = order.permutation(lognormal_quantiles(mix["prompt_len"], n))
    a = order.permutation(lognormal_quantiles(mix["new_tokens"], n))
    rng = np.random.default_rng([seed, 0x5E, stream])
    out = []
    for plen, alen in zip(p, a):
        toks = corpus_lib.zipf_segments(int(plen), vocab_size, rng)
        out.append(Spec(toks, int(alen)))
    return out


def arrival_offsets(mix: dict, n: int, stream: int) -> np.ndarray:
    """Open loop: due times (s, from the start of the set) of ``n``
    requests: the fixed gaps in a fixed order, cumulated."""
    order = np.random.default_rng([0xA7, stream])
    gaps = order.permutation(exponential_gaps(mix["arrival"]["rate_per_s"], n))
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def open_count(mix: dict, seconds: float) -> int:
    return max(1, int(math.ceil(mix["arrival"]["rate_per_s"] * seconds)))
