"""Data and traffic from ``--seed``: one general generator read by every mix.

The same seed gives the same data and the same schedule; every seed gets
the same operations of each kind and the same set of gaps between them,
only their places and order differ, so that seeds change the work as
little as possible.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def schwefel(x: np.ndarray) -> np.ndarray:
    """418.9829 - (1/D) sum_d x_d sin(sqrt|x_d|) on (-500, 500)^D (the
    paper's Sec. 7 normalization)."""
    x = np.atleast_2d(x)
    return 418.9829 - np.sum(x * np.sin(np.sqrt(np.abs(x))), axis=-1) / x.shape[-1]


def rastrigin(x: np.ndarray) -> np.ndarray:
    """10 - (1/D) sum_d (x_d^2 - 10 cos(2 pi x_d)) on (-5.12, 5.12)^D."""
    x = np.atleast_2d(x)
    return 10.0 - np.sum(x ** 2 - 10.0 * np.cos(2 * np.pi * x), axis=-1) / x.shape[-1]


FUNCTIONS = {"schwefel": (schwefel, 500.0), "rastrigin": (rastrigin, 5.12)}

# separate streams of one seed, so that drawing more of one never shifts
# another
STREAMS = {"data": 0, "warmup": 1, "schedule": 2, "check": 3, "probes": 4}


def rng(seed: int, stream: str, *key: int) -> np.random.Generator:
    """The seed's generator for ``stream``; ``key`` (such as a round's
    index) splits a stream into independent ones."""
    return np.random.default_rng(
        np.random.SeedSequence(int(seed) % 2 ** 64,
                               spawn_key=(STREAMS[stream],) + key))


def bounds(config: dict) -> np.ndarray:
    half = FUNCTIONS[config["function"]][1]
    return np.stack([np.full(config["D"], -half), np.full(config["D"], half)],
                    axis=1)


def observe(config: dict, g: np.random.Generator, m: int):
    """``m`` uniform points of the box and their noisy observations."""
    f, half = FUNCTIONS[config["function"]]
    x = g.uniform(-half, half, size=(m, config["D"]))
    return x, f(x) + config["noise_std"] * g.standard_normal(m)


def omega(config: dict) -> np.ndarray:
    """The paper's Fig. 5 rule: omega_d = omega_span / span_d."""
    span = bounds(config)[:, 1] - bounds(config)[:, 0]
    return config["omega_span"] / span


@dataclasses.dataclass
class Schedule:
    """Open-loop operations, in order of their due times."""

    t: np.ndarray      # (N,) seconds after the window opens
    insert: np.ndarray  # (N,) bool: an insert, else a query
    kind: list         # (N,) query kind (None for an insert)
    x: np.ndarray      # (N, D) query place or new observation's place
    y: np.ndarray      # (N,) new observation (NaN for a query)


def gaps(rate: float, block: int, n: int) -> np.ndarray:
    """The inter-arrival gaps of ``n`` operations, block by block: each
    block of ``block`` operations (the last one may be shorter) holds the
    same gaps, the quantiles of an exponential distribution of mean
    ``1 / rate`` at (k + 1/2) / block, scaled to that mean exactly."""
    out = []
    for m in [block] * (n // block) + ([n % block] if n % block else []):
        q = -np.log1p(-(np.arange(m) + 0.5) / m)
        out.append(q / (q.mean() * rate))
    return np.concatenate(out)


def open_loop(config: dict, traffic: dict, seed: int, seconds: float,
              rate: float | None = None) -> Schedule:
    """Open-loop arrivals at ``rate`` per second over ``seconds``, the same
    work for every seed in another order. N = round(rate * seconds)
    operations; their gaps are the fixed set of ``gaps`` for the mix's
    ``block_ops``, shuffled within each block, so that any stretch of the
    window holds about its share of the load (exponential gaps, as in a
    Poisson process, within a block). Exactly round(N * insert_share) of
    them are inserts, one in each of as many equal strata of the
    operations, at a place in its stratum drawn from the seed; queries
    cycle through the mix's kinds in a shuffled order."""
    rate = traffic["rate_per_s"] if rate is None else rate
    g = rng(seed, "schedule")
    n = max(1, int(round(rate * seconds)))
    block = int(traffic["block_ops"])
    gap = gaps(rate, block, n)
    for lo in range(0, n, block):
        gap[lo:lo + block] = g.permutation(gap[lo:lo + block])
    t = np.cumsum(gap) - gap
    n_ins = int(round(n * traffic["insert_share"]))
    insert = np.zeros(n, bool)
    if n_ins:
        edges = np.floor(np.arange(n_ins + 1) * n / n_ins).astype(int)
        insert[edges[:-1] + np.floor(g.uniform(size=n_ins)
                                     * np.diff(edges)).astype(int)] = True
    kinds = traffic["query_kinds"]
    kind = [None] * n
    for j, i in enumerate(g.permutation(np.flatnonzero(~insert))):
        kind[i] = kinds[j % len(kinds)]
    x, y = observe(config, g, n)
    y = np.where(insert, y, np.nan)
    return Schedule(t=t, insert=insert, kind=kind, x=x, y=y)
