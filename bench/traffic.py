"""The one traffic generator: a mix file (``bench/traffic/<mix>.json``)
and a seed in, an open-loop schedule of requests out.

Every seed draws the same multiset of sizes and the same gaps between
arrivals, in another order, and its own prompt tokens: lengths are
stratified over each block of ``block`` consecutive requests (the
largest-remainder split of the prompt-length weights, and ``block``
evenly spaced quantiles of the output-length distribution), and the
Poisson gaps are the quantiles of the exponential distribution. So seeds
change which request is which, not how much work a run holds.

A mix holds:
  start          "cold": the window opens with no replica on the device;
                 "warm": a replica is up and every tier-1 unit resident
  slots          decode slots of the scheduler
  rate_rps       Poisson arrivals, requests per second; the first request
                 is due when the window opens
  prompt_lens, prompt_weights
  prompt_zipf_s  prompt tokens are Zipfian over the vocabulary with this
                 exponent, token id = frequency rank - 1: the distribution
                 the program's ``stats`` plan is profiled on
                 (``repro.data.pipeline``)
  output_median, output_sigma, output_min, output_max
                 lognormal output lengths, clipped
  block          requests per stratification block
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

BENCH = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Request:
    due: float          # seconds after the window opens
    prompt: np.ndarray  # (S,) int32 token ids
    out_len: int        # tokens to emit, the prefill's included


def load_mix(name: str, root: Path = BENCH) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def max_seq(mix: dict) -> int:
    """Positions the longest request of the mix occupies."""
    return max(mix["prompt_lens"]) + mix["output_max"]


def _split(weights: list[float], n: int) -> list[int]:
    """Largest-remainder split of ``n`` by ``weights``."""
    w = np.asarray(weights, float) / sum(weights)
    raw = w * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def _block_sizes(mix: dict, n: int, rng) -> list[tuple[int, int]]:
    """(prompt length, output length) of ``n`` requests, block by block."""
    out = []
    block = mix["block"]
    nd = NormalDist()
    for b0 in range(0, n, block):
        m = min(block, n - b0)
        lens = [L for L, c in zip(mix["prompt_lens"], _split(mix["prompt_weights"], m))
                for _ in range(c)]
        outs = [
            int(np.clip(round(mix["output_median"]
                              * math.exp(mix["output_sigma"] * nd.inv_cdf((j + 0.5) / m))),
                        mix["output_min"], mix["output_max"]))
            for j in range(m)
        ]
        out += list(zip(rng.permutation(lens).tolist(), rng.permutation(outs).tolist()))
    return out


def _zipf_cdf(vocab: int, s: float) -> np.ndarray:
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -s
    return np.cumsum(w) / w.sum()


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list[Request]:
    """Requests due in ``[0, seconds)``, in order of due time."""
    rng = np.random.default_rng(seed)
    rate = mix["rate_rps"]
    m = max(0, int(round(rate * seconds)) - 1)  # gaps after the first request
    gaps = [-math.log(1.0 - (j + 0.5) / m) / rate for j in range(m)]
    dues = [d for d in np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))]) if d < seconds]
    sizes = _block_sizes(mix, len(dues), rng)
    cdf = _zipf_cdf(vocab, mix["prompt_zipf_s"])
    return [
        Request(due=float(d), out_len=o, prompt=np.minimum(
            np.searchsorted(cdf, rng.random(S)), vocab - 1).astype(np.int32))
        for d, (S, o) in zip(dues, sizes)
    ]
