"""The one general traffic generator: a traffic file's parameters and a
seed give the requests of a run.

Every seed gets the SAME set of sizes and arrival gaps in another order
(so runs with different seeds do the same work): prompt and output
lengths are quantile-stratified draws from the file's clipped
log-normals (`size_pool` of them in a closed loop, one for each arrival of
the window in an open loop), paired by the file's fixed `pairing_seed`; an
open loop's gaps are the stratified quantiles of the exponential
distribution at the file's rate (`arrival: poisson`), or bursts of
`burst.min`..`burst.max` arrivals inside `burst.span_s` whose starts are
spaced that way (`arrival: bursts`). The seed permutes both and draws the
token ids. With `shared_prefix: {tokens, groups}` every prompt begins with
one of `groups` prefixes of that many tokens (the lengths then describe
the unshared part).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np

SEED_SPACE = 2 ** 31 - 1     # the batcher carries request seeds as int32


@dataclasses.dataclass
class Spec:
    """One request to send: sizes, when it is due (open loop: seconds
    from the window's start; closed loop: None), and its own seeds."""
    index: int
    prompt_len: int
    out_len: int
    due: Optional[float]
    token_seed: int
    sample_seed: int
    prefix_seed: Optional[int] = None
    prefix_len: int = 0

    def prompt(self, vocab: int) -> List[int]:
        own = np.random.default_rng(self.token_seed).integers(
            3, vocab, self.prompt_len).tolist()
        if not self.prefix_len:
            return own
        return np.random.default_rng(self.prefix_seed).integers(
            3, vocab, self.prefix_len).tolist() + own


def stratified_lengths(dist: dict, n: int) -> List[int]:
    """n lengths at the mid-quantiles of a clipped log-normal."""
    if dist.get("dist") != "lognormal":
        raise ValueError(f"unknown length distribution {dist.get('dist')!r}")
    mu, sigma = math.log(dist["median"]), float(dist["sigma"])
    nd = NormalDist()
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(dist["max"], max(dist["min"], round(x)))))
    return out


def size_pool(traffic: dict, n: int) -> List[tuple]:
    """The fixed multiset of n (prompt_len, out_len) pairs of a mix."""
    prompts = stratified_lengths(traffic["prompt_len"], n)
    outs = stratified_lengths(traffic["output_len"], n)
    order = np.random.default_rng(int(traffic["pairing_seed"])).permutation(n)
    return [(prompts[i], outs[int(order[i])]) for i in range(n)]


def _exp_gaps(n: int, seconds: float) -> List[float]:
    """n stratified exponential gaps that fill `seconds`."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = seconds / (sum(gaps) * (1.0 + 1.0 / n))
    return [g * scale for g in gaps]


def arrival_times(traffic: dict, seconds: float, rng) -> List[float]:
    """Due times of the rate * seconds arrivals of an open loop, from the
    window's start. The multiset of gaps (and of burst sizes) is the same
    for every seed; `rng` only orders it."""
    n = max(1, int(round(float(traffic["rate_rps"]) * seconds)))
    kind = traffic.get("arrival")
    if kind == "poisson":
        sizes, span = [1] * n, 0.0
    elif kind == "bursts":
        lo, hi = int(traffic["burst"]["min"]), int(traffic["burst"]["max"])
        span = float(traffic["burst"]["span_s"])
        sizes, k = [], lo
        while sum(sizes) < n:
            sizes.append(min(k, n - sum(sizes)))
            k = lo if k >= hi else k + 1
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    gaps = _exp_gaps(len(sizes), seconds)
    gaps = [gaps[int(j)] for j in rng.permutation(len(gaps))]
    sizes = [sizes[int(j)] for j in rng.permutation(len(sizes))]
    out, t = [], 0.0
    for g, k in zip(gaps, sizes):
        t += g
        out += [t + span * (j + 0.5) / k for j in range(k)] if span \
            else [t] * k
    return sorted(min(x, seconds * (1 - 1e-9)) for x in out)


def _specs(traffic: dict, seed: int, n: int) -> Iterator[Spec]:
    """Endless stream of sized requests: the pool of n in the seed's
    order, again and again, with fresh token ids each time round."""
    pool = size_pool(traffic, n)
    rng = np.random.default_rng(seed)
    shared = traffic.get("shared_prefix")
    groups = ([int(x) for x in rng.integers(0, SEED_SPACE, shared["groups"])]
              if shared else [])
    i = 0
    while True:
        for j in rng.permutation(len(pool)):
            p, o = pool[int(j)]
            ts, ss = (int(x) for x in rng.integers(0, SEED_SPACE, 2))
            spec = Spec(i, p, o, None, ts, ss)
            if shared:
                spec.prefix_seed = groups[i % len(groups)]
                spec.prefix_len = int(shared["tokens"])
            yield spec
            i += 1


def closed_loop(traffic: dict, seed: int) -> Iterator[Spec]:
    return _specs(traffic, seed, int(traffic["size_pool"]))


def open_loop(traffic: dict, seed: int, seconds: float) -> List[Spec]:
    """The arrivals due inside a window of `seconds`, in order."""
    dues = arrival_times(traffic, seconds,
                         np.random.default_rng([seed, 1]))
    out = []
    for spec, due in zip(_specs(traffic, seed, len(dues)), dues):
        spec.due = due
        out.append(spec)
    return out
