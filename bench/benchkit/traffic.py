"""One generator for every traffic mix: reads a mix's parameters
(`bench/traffic/<mix>.json`) and makes the requests of a run from its
seed.

Every seed gets the same work: lengths are the quantiles (i + 0.5)/n of
the mix's distribution, inter-arrival gaps the quantiles of an
exponential, and tenants come in exact shares, each list in an order
drawn once from the mix's own ``layout_seed``.  The run's seed draws the
token ids (and the benchmark's weights).  Where long prompts fall among
the others decides much of a tail, so a seed that also reordered them
would change the work itself.

Two kinds of arrivals:

* ``poisson`` — open loop: ``rate_rps`` × seconds requests due inside the
  window, the gaps scaled so that the last is due before it closes.
* ``backlog`` — offline: a queue held at ``depth`` waiting requests; the
  stream is a pool of ``pool`` requests, repeated in order if a run needs
  more.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Planned:
    """One request of a run, before it becomes the program's `Request`."""

    rid: int
    tenant: str
    prompt: list[int]
    max_new: int
    due: float = 0.0  # seconds after the window opens (poisson only)


def length_quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5)/n of a clipped lognormal
    given by its ``median`` or its ``mean`` and ``sigma``."""
    sigma = float(dist["sigma"])
    if "median" in dist:
        mu = math.log(float(dist["median"]))
    else:
        mu = math.log(float(dist["mean"])) - sigma * sigma / 2
    nd = NormalDist()
    q = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(q), int(dist["min"]), int(dist["max"])).astype(int)


def gap_quantiles(n: int, span: float) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at the quantiles (i + 0.5)/n,
    scaled to sum to ``span``."""
    g = np.asarray([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return g * (span / g.sum())


def tenant_counts(tenants: dict, n: int) -> list[str]:
    """Exact shares by largest remainder, as a list of ``n`` names."""
    names = list(tenants)
    share = np.asarray([float(tenants[t]["share"]) for t in names])
    raw = share / share.sum() * n
    cnt = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - cnt), kind="stable")[: n - cnt.sum()]:
        cnt[i] += 1
    return [t for t, c in zip(names, cnt) for _ in range(int(c))]


def weights(mix: dict) -> dict[str, float]:
    return {t: float(v["weight"]) for t, v in mix["tenants"].items()}


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) & (2**64 - 1), salt]))


def plan(mix: dict, seed: int, seconds: float, vocab: int) -> list[Planned]:
    """The requests of one run: every one due in the window (poisson), or
    the backlog's whole pool (backlog)."""
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        n = max(1, int(round(float(arr["rate_rps"]) * seconds)))
    elif arr["kind"] == "backlog":
        n = int(arr["pool"])
    else:
        raise ValueError(f"unknown arrivals {arr['kind']!r}")
    order = _rng(int(mix["layout_seed"]), 1)
    prompts = order.permutation(length_quantiles(mix["prompt"], n))
    outputs = order.permutation(length_quantiles(mix["output"], n))
    tenants = order.permutation(tenant_counts(mix["tenants"], n))
    dues = np.zeros(n)
    if arr["kind"] == "poisson":
        gaps = order.permutation(gap_quantiles(n, seconds))
        dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    toks = _rng(seed, 2)
    return [Planned(rid=i, tenant=str(tenants[i]),
                    prompt=[int(t) for t in toks.integers(1, vocab,
                                                          int(prompts[i]))],
                    max_new=int(outputs[i]), due=float(dues[i]))
            for i in range(n)]


def backlog_stream(pool: list[Planned]):
    """The backlog's requests in order, the pool repeated under fresh rids
    for as long as a run takes them."""
    k = 0
    while True:
        for p in pool:
            yield Planned(rid=k, tenant=p.tenant, prompt=p.prompt,
                          max_new=p.max_new)
            k += 1
