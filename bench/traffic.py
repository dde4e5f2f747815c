"""Traffic generators, driven by a mix's parameter file and the seed.

``SyntheticLM`` is a copy of the program's ``data.pipeline.SyntheticLM``
(a noisy affine bigram walk): the training cells' batches.  ``schedule``
is the open-loop serving generator: Poisson arrivals, prompt and output
lengths log-normal about the medians a mix takes from its source, the
prompts put in the nearest of a few buckets.  Every seed offers the same
requests' lengths and gaps; only their order and token ids differ.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class SyntheticLM:
    """Noisy affine bigram stream: x_{t+1} = (a*x_t + b + eps) mod V."""

    vocab: int
    seq_len: int
    batch: int
    seed: int = 0
    a: int = 31
    b: int = 7
    noise: int = 3          # eps in [0, noise)

    def batch_for_step(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.Generator(np.random.Philox(
            key=self.seed, counter=[step, 0, 0, 0]))
        b, s, v = self.batch, self.seq_len, self.vocab
        x0 = rng.integers(0, v, size=(b,), dtype=np.int64)
        eps = rng.integers(0, max(self.noise, 1), size=(b, s), dtype=np.int64)
        toks = np.empty((b, s + 1), np.int64)
        toks[:, 0] = x0
        for t in range(s):
            toks[:, t + 1] = (self.a * toks[:, t] + self.b + eps[:, t]) % v
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: int
    due_s: float             # seconds after the window opens
    prompt: np.ndarray       # (prompt_len,) int32
    gen: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_quantiles(median: float, sigma: float, n: int) -> np.ndarray:
    """The n quantiles of a log-normal with this median and log-spread."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    return median * np.exp(sigma * z)


def nearest_bucket(lengths: np.ndarray, buckets) -> np.ndarray:
    """Each length put in the bucket nearest to it on a log scale; those
    beyond the largest bucket are cut to it."""
    b = np.asarray(buckets, np.float64)
    i = np.argmin(np.abs(np.log(lengths)[:, None] - np.log(b)[None]), axis=1)
    return b[i].astype(np.int64)


def draw_lengths(mix: dict, n: int):
    """The same n prompt and output lengths for every seed: the n
    quantiles of log-normals about the mix's medians, prompts in their
    nearest bucket, outputs rounded and clipped to [gen_min, gen_max]."""
    plen = nearest_bucket(lognormal_quantiles(
        mix["prompt_median"], mix["prompt_sigma"], n), mix["prompt_buckets"])
    gen = np.rint(lognormal_quantiles(mix["gen_median"], mix["gen_sigma"], n))
    return plen, np.clip(gen, mix["gen_min"], mix["gen_max"]).astype(np.int64)


def schedule(mix: dict, vocab: int, seed: int, seconds: float) -> List[Arrival]:
    """Open-loop arrivals over a window of ``seconds`` at the mix's
    ``rate_per_s``: n = rate x seconds requests whose gaps are the n
    quantiles of the exponential (a Poisson process's gaps), scaled to end
    inside the window.  Every seed gets the same gaps and lengths in
    another order, and its own token ids, so a seed changes the order of
    the work and never its amount."""
    rng = np.random.Generator(np.random.Philox(key=seed,
                                               counter=[0, 0, 0, 7]))
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    gaps = -np.log(1.0 - _quantiles(n))
    gaps *= seconds * n / (n + 1) / gaps.sum()
    due = np.cumsum(rng.permutation(gaps)) - gaps.min() / 2
    plen, gen = draw_lengths(mix, n)
    plen, gen = rng.permutation(plen), rng.permutation(gen)
    return [Arrival(i, float(due[i]),
                    rng.integers(0, vocab, size=int(plen[i])).astype(np.int32),
                    int(gen[i])) for i in range(n)]
