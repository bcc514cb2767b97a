"""The one general traffic generator: a mix file (`traffic/<name>.json`)
and a seed -> requests and, for an open loop, their arrival times.

A mix says how a request picks its domain and its keys inside the
domain, how many descriptors it carries, and how it is offered:

  loop "closed": `clients` callers, each sending its next request when
                 the previous one is answered;
  loop "open":   Poisson arrivals at `rate_rps`, whatever the server does.

Picks (`domain_pick`, `key_pick`):
  {"dist": "uniform"}
  {"dist": "zipf", "alpha": a}       rank r with weight r^-a; which item
                                     holds which rank is a permutation
                                     drawn from the seed
  {"dist": "hotset", "hot_share": s, "hot_fraction": f}
                                     s of the picks go to a seed-chosen
                                     f of the items, the rest uniform
                                     over all of them

Pure functions of (mix, deployment, seed): the same seed gives the same
arrivals and the same request bytes, in every process that asks.
"""

from __future__ import annotations

import numpy as np

from .deploy import Deployment


def _picker(spec: dict, n: int, rng: np.random.Generator):
    """A function size -> item numbers in [0, n), per `spec`."""
    dist = spec["dist"]
    if dist == "uniform" or n == 1:
        return lambda size: rng.integers(0, n, size)
    perm = rng.permutation(n)
    if dist == "zipf":
        p = np.arange(1, n + 1, dtype=float) ** -float(spec["alpha"])
        p /= p.sum()
        return lambda size: perm[rng.choice(n, size, p=p)]
    if dist == "hotset":
        hot = perm[: max(1, int(n * float(spec["hot_fraction"])))]
        share = float(spec["hot_share"])

        def pick(size):
            cold = rng.integers(0, n, size)
            warm = hot[rng.integers(0, len(hot), size)]
            return np.where(rng.random(size) < share, warm, cold)

        return pick
    raise ValueError(f"unknown dist {dist!r}")


def offered(mix: dict, seconds: float, seed: int, rehearse: bool, rate_rps=None):
    """(number of requests the run plans, arrival offsets or None):
    an open loop plans one request per arrival of warm-up + window, a
    closed loop a pool of `pool_requests` that its callers cycle."""
    if mix["loop"] == "open":
        rate = float(rate_rps or mix["rate_rps"])
        offsets = arrivals(rate, float(mix["warm_s"]) + seconds, seed)
        return len(offsets), offsets
    return int(mix["pool_requests"]) // (32 if rehearse else 1), None


def plan(mix: dict, dep: Deployment, seed: int, n: int):
    """`n` requests: domains int64[n], keys int64[n, descriptors].
    (The draws depend on n: plan the same n wherever the same requests
    are meant — `offered` gives it.)"""
    rng = np.random.default_rng([int(seed), 1])
    pick_domain = _picker(mix["domain_pick"], dep.n_domains, rng)
    pick_key = _picker(mix["key_pick"], dep.kpd, rng)
    per = int(mix["descriptors_per_request"])
    return pick_domain(n), pick_key(n * per).reshape(n, per)


def arrivals(rate_rps: float, span_s: float, seed: int) -> np.ndarray:
    """Poisson arrival offsets in [0, span_s), from the seed."""
    rng = np.random.default_rng([int(seed), 2])
    n = int(rate_rps * span_s * 1.2) + 64
    t = np.cumsum(rng.exponential(1.0 / rate_rps, n))
    while t[-1] < span_s:  # vanishingly rare; extend deterministically
        t = np.concatenate([t, t[-1] + np.cumsum(rng.exponential(1.0 / rate_rps, n))])
    return t[t < span_s]


def make_request(dep: Deployment, d: int, keys) -> bytes:
    """The serialized ShouldRateLimit request for domain d, keys `keys`."""
    from . import wire

    req = wire.rls_pb2.RateLimitRequest(domain=dep.domain_name(int(d)))
    for k in keys:
        desc = req.descriptors.add()
        for key, value in dep.entries(int(k)):
            entry = desc.entries.add()
            entry.key, entry.value = key, value
    return req.SerializeToString()
