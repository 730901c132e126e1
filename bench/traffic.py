"""The one traffic generator: it reads a mix file of ``bench/traffic/`` and a seed.

A mix file states an open loop's load and two length distributions:

    {"rate_per_s": 1.9, "preroll_s": 20, "drain_s": 60,
     "prompt_tokens": {"median": 16, "sigma": 0.6, "min": 4, "max": 128},
     "output_tokens": {"median": 39, "sigma": 0.9, "min": 4, "max": 256}}

Arrivals are Poisson at ``rate_per_s``. With ``"burst": k`` (default 1) they
come in bursts of k requests due at the same instant, the bursts Poisson at
``rate_per_s / k``: so many requests at once that the engine queues them
however fast it serves. Lengths are log-normal with the given median and
sigma, rounded and clipped to [min, max]. The schedule has three parts: the
preroll, ``preroll_s`` seconds before the measured window opens, so that the
window opens on a loaded engine; the window; and the drain, up to ``drain_s``
seconds after it closes, which keeps the load on while the window's last
requests wait for their first token.

Each part holds a fixed number of arrivals, round(rate x its length), the
last burst holding what is left, and a fixed set of lengths and gaps: the
quantiles of their distributions at evenly spaced probabilities, the gaps
scaled to fill the part. The seed draws the order of the gaps and of the
lengths in each part, and the token ids. So every seed offers the same work in
another order.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from statistics import NormalDist
from typing import List

import numpy as np

MIX_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Arrival:
    due_s: float            # seconds after the window opens; negative in the preroll
    prompt: List[int]
    max_new_tokens: int


def load_mix(name: str) -> dict:
    return json.loads((MIX_DIR / f"{name}.json").read_text())


def length_grid(dist: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 1/2)/n of the clipped log-normal."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    raw = dist["median"] * np.exp(dist["sigma"] * np.asarray(z))
    return np.clip(np.rint(raw), dist["min"], dist["max"]).astype(int)


def gap_grid(rate: float, n: int, span: float) -> np.ndarray:
    """n gaps between Poisson arrivals at the quantiles (i + 1/2)/n, scaled so
    that they add up to `span`: n arrivals fill the span in any order."""
    g = np.asarray([-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)])
    return g * (span / g.sum())


def schedule(mix: dict, seed: int, vocab_size: int, seconds: float) -> List[Arrival]:
    """The run's requests in the order they are due: the preroll's, the
    window's and the drain's, each part a fixed set in the seed's order."""
    rng = np.random.default_rng(seed)
    rate, burst = mix["rate_per_s"], mix.get("burst", 1)
    out: List[Arrival] = []
    for start, span in ((-mix["preroll_s"], mix["preroll_s"]), (0.0, seconds),
                        (seconds, mix["drain_s"])):
        n = max(1, round(rate * span))
        bursts = -(-n // burst)
        due = start + np.concatenate([[0.0], np.cumsum(rng.permutation(
            gap_grid(rate / burst, bursts, span)))[:-1]])
        due = np.repeat(due, burst)[:n]
        prompts = rng.permutation(length_grid(mix["prompt_tokens"], n))
        outputs = rng.permutation(length_grid(mix["output_tokens"], n))
        out += [Arrival(float(t), rng.integers(0, vocab_size, int(p)).tolist(), int(o))
                for t, p, o in zip(due, prompts, outputs)]
    return out
