"""The one traffic generator: it reads a mix's parameters (a
``traffic/<mix>.json``) and a cell's pinned numbers, and draws requests from
``--seed``.

The sizes and the schedule are the same for every seed, in the same order:
request i's prompt and output lengths are the distributions' quantiles at
two low-discrepancy sequences (the fractional parts of 0.5 + i x alpha), so
every stretch of the stream holds the whole spread of lengths; the
inter-arrival gaps are the gap distribution's quantiles in one fixed order.
The seed draws only the token ids, so it never changes how much work a run
offers or when.

Mix keys:
  loop: "closed" (a backlog of ``backlog_per_slot`` x slots is kept queued)
        or "open" (arrivals on a schedule, at the cell's ``rate_per_s``).
  prompt, output: {"dist": "lognormal", "median", "sigma", "min", "max"}.
  arrivals (open loop): {"dist": "gamma", "cv"}: gamma gaps of that
        coefficient of variation, scaled so that the run's requests span
        exactly its window.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator

import numpy as np
from scipy import stats

# the low-discrepancy sequences of the prompt and output lengths: the
# golden ratio's and sqrt(2)'s fractional parts, rationally independent,
# so the (prompt, output) pairs fill the unit square evenly
ALPHA = {"prompt": 0.6180339887498949, "output": 0.4142135623730951}
# the key of the one fixed order of the gaps
GAP_ORDER = 20190604


@dataclasses.dataclass(frozen=True)
class Spec:
    rid: int
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int
    at: float                   # scheduled offset from the window's start
                                # (s); 0 for a closed loop


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(d: dict, u: np.ndarray) -> np.ndarray:
    """The lengths of the distribution ``d`` at quantiles u, clipped."""
    if d["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    x = stats.lognorm.ppf(u, s=d["sigma"], scale=d["median"])
    return np.clip(np.rint(x), d["min"], d["max"]).astype(np.int64)


def sizes(mix: dict, lo: int, hi: int) -> tuple:
    """(prompt lengths, output lengths) of requests lo..hi-1."""
    i = np.arange(lo, hi)
    return tuple(lengths(mix[k], (0.5 + i * ALPHA[k]) % 1.0)
                 for k in ("prompt", "output"))


def gaps(d: dict, n: int, span: float) -> np.ndarray:
    """n inter-arrival gaps with the stated burstiness, summing to span, in
    one fixed order."""
    if d["dist"] != "gamma":
        raise ValueError(f"unknown arrival distribution {d['dist']!r}")
    shape = 1.0 / d["cv"] ** 2
    g = stats.gamma.ppf(_quantiles(n), a=shape)
    g = np.random.default_rng(GAP_ORDER).permutation(g)
    return g * (span / g.sum())


def _ids(seed: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), 2])


def open_loop(mix: dict, rate: float, seconds: float, seed: int,
              vocab: int) -> list:
    """round(rate x seconds) requests, the first due at 0 and every one due
    inside [0, seconds)."""
    n = max(1, int(round(rate * seconds)))
    g = gaps(mix["arrivals"], n, seconds)
    at = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    p, o = sizes(mix, 0, n)
    ids = _ids(seed)
    return [Spec(rid=i, prompt=ids.integers(0, vocab, int(p[i]),
                                            dtype=np.int32),
                 max_new_tokens=int(o[i]), at=float(at[i]))
            for i in range(n)]


def closed_loop(mix: dict, seed: int, vocab: int) -> Iterator[Spec]:
    """The closed loop's request stream, without end, in the order it is
    sent."""
    ids = _ids(seed)
    for i in itertools.count():
        p, o = sizes(mix, i, i + 1)
        yield Spec(rid=i, prompt=ids.integers(0, vocab, int(p[0]),
                                              dtype=np.int32),
                   max_new_tokens=int(o[0]), at=0.0)
