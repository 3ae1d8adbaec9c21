"""The one traffic generator: open-loop arrivals and request sizes from a
mix's parameters and the run's seed.

Every seed gets the same work on the same schedule.  A window of
``seconds`` at ``rate_rps`` holds ``n = floor(rate_rps * seconds)``
requests.  Their prompt lengths, output lengths and inter-arrival gaps are
the stratified quantiles ``(j + 0.5) / n`` of the mix's distributions, the
gaps scaled to a mean of exactly ``1 / rate_rps`` so that the last request
is due inside the window.  The mix's ``schedule_seed`` permutes each of
the three lists on its own, the same for every run; the run's seed draws
the token ids (and, elsewhere, the weights and the check's sample).  So
runs on different seeds differ in contents, not in the amount, sizes or
timing of the work.

A mix file reads::

    {"interarrival": {"dist": "gamma", "shape": 1.0},
     "prompt_len": {"dist": "lognormal", "median": 1020, "sigma": 0.8,
                    "min": 16, "max": 4096},
     "output_len": {"dist": "lognormal", "median": 129, "sigma": 0.7,
                    "min": 16, "max": 512},
     "schedule_seed": 0, ...}

Gamma inter-arrival gaps of shape 1 are a Poisson process; a shape below 1
makes arrivals burstier at the same mean rate.  The rate itself is the
cell's (``cells/<workload>.json``), since one mix is offered to several
configurations and loads.
"""
from __future__ import annotations

import dataclasses

import numpy as np
from scipy import stats


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    rid: int
    due_s: float          # offset from the start of the window
    prompt: np.ndarray    # (P,) int32 token ids
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles, rounded and clipped."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    q = stats.lognorm.ppf(_quantiles(n), spec["sigma"],
                          scale=spec["median"])
    return np.clip(np.rint(q), spec["min"], spec["max"]).astype(np.int64)


def gaps(spec: dict, n: int, rate_rps: float) -> np.ndarray:
    """``n`` inter-arrival gaps in seconds, scaled so that their mean is
    exactly ``1 / rate_rps``."""
    if spec["dist"] != "gamma":
        raise ValueError(f"unknown inter-arrival distribution "
                         f"{spec['dist']!r}")
    g = stats.gamma.ppf(_quantiles(n), spec["shape"])
    return g / g.mean() / rate_rps


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for ``seed`` (any non-negative size) and a stream
    id, so that the traffic, the failures and the check's sample draw from
    independent streams of one seed."""
    return np.random.default_rng([int(seed), *stream])


def generate(mix: dict, *, rate_rps: float, seconds: float, seed: int,
             vocab_size: int) -> list[RequestSpec]:
    """The window's requests, in order of due time."""
    n = max(1, int(rate_rps * seconds))
    order = seed_rng(mix["schedule_seed"], 0)
    p = order.permutation(lengths(mix["prompt_len"], n))
    o = order.permutation(lengths(mix["output_len"], n))
    g = order.permutation(gaps(mix["interarrival"], n, rate_rps))
    due = np.concatenate([[0.0], np.cumsum(g[:-1])])
    rng = seed_rng(seed, 0)
    return [RequestSpec(rid=j, due_s=float(due[j]),
                        prompt=rng.integers(1, vocab_size, int(p[j]))
                        .astype(np.int32),
                        max_new=int(o[j]))
            for j in range(n)]
