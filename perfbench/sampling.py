"""Seeded draws shared by the traffic generators (numpy and the standard
library only; the parent process imports this).

Every seed gets the same SET of sizes and gaps, in another order: a
distribution is sampled at the midpoints of `block` equal-probability strata
(its quantile function at (i + 0.5) / block), and each consecutive block of
`block` draws is one shuffle of those values. So any stretch of a few blocks
holds the same work whatever the seed, and run-to-run differences come from
the system and not from what the seed happened to draw.

The order of each block is fixed (`ORDER_SEED`), and the run's seed only
shuffles inside each run of `SHUFFLE_GROUP` consecutive draws: with whole
blocks shuffled by the seed, runs of one seed differed by 0.2-1.4% and seeds
by up to 5.7% in `mixtral8x7b-batch` (my chip runs, PR 23), because which
prompts and answer lengths fell into the window depended on the seed. Bursts
and lulls longer than two requests are now the same for every seed, as the
tail of a queue needs too.
"""

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()
SHUFFLE_GROUP = 2    # the seed swaps neighbours only
ORDER_SEED = 23      # the order every run measured on the chip had (PR 23)


def rng_of(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator from any whole-number seed; `stream` keeps independent
    uses (lengths, gaps, token ids) apart."""
    return np.random.default_rng([int(seed), stream])


def _blocks(values: np.ndarray, n: int, rng: np.random.Generator) -> np.ndarray:
    n_blocks = -(-n // len(values))
    fixed = np.random.default_rng([ORDER_SEED, len(values)])
    out = np.concatenate([fixed.permutation(values) for _ in range(n_blocks)])
    for start in range(0, len(out), SHUFFLE_GROUP):
        out[start:start + SHUFFLE_GROUP] = rng.permutation(
            out[start:start + SHUFFLE_GROUP])
    return out[:n]


def lognormal_ints(n: int, spec: dict, rng, block: int = 32) -> list:
    """`n` whole numbers from a lognormal with `median` and `sigma`, clipped
    to [`min`, `max`], stratified in blocks."""
    q = (np.arange(block) + 0.5) / block
    z = np.asarray([_NORMAL.inv_cdf(p) for p in q])
    values = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    values = np.clip(np.rint(values), spec["min"], spec["max"]).astype(int)
    return _blocks(values, n, rng).tolist()


def uniform_ints(n: int, lo: int, hi: int, rng, block: int = 32) -> list:
    values = np.rint(lo + (np.arange(block) + 0.5) / block * (hi - lo)).astype(int)
    return _blocks(values, n, rng).tolist()


def exponential_gaps(n: int, rng, block: int = 32) -> np.ndarray:
    """`n` gaps of a Poisson process of rate 1 (mean 1), stratified."""
    q = (np.arange(block) + 0.5) / block
    values = -np.log1p(-q)
    values /= values.mean()          # the midpoints' mean is a little under 1
    return _blocks(values, n, rng)


def arrivals(seconds: float, rate: float, burst: dict, rng) -> list:
    """Due times in [0, seconds) of an open loop: Poisson at `rate` a second,
    times `burst["factor"]` between `burst["from"]` and `burst["to"]` (shares
    of the window)."""
    lo, hi = burst["from"] * seconds, burst["to"] * seconds
    mean_rate = rate * (1 + (burst["factor"] - 1) * (burst["to"] - burst["from"]))
    gaps = exponential_gaps(int(seconds * mean_rate * 1.5) + 64, rng)
    t, out = 0.0, []
    for gap in gaps:
        t += gap / (rate * (burst["factor"] if lo <= t < hi else 1.0))
        if t >= seconds:
            break
        out.append(t)
    return out


def token_ids(n: int, vocab: int, rng) -> list:
    return rng.integers(0, vocab, n).tolist()
