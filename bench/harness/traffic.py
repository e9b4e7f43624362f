"""The one traffic generator: reads a mix's parameters, makes requests.

Every seed gets the same prompt lengths, output lengths and
inter-arrival gaps (fixed quantiles of the mix's distributions) in the
same order, so two seeds do the same work; the seed draws only the prompt
tokens (and, in the harness, the weights).

Distributions (all from the mix's JSON file):

* lengths: lognormal with the given ``median`` and ``sigma``, clipped to
  ``[min, max]`` and rounded to whole tokens;
* arrivals: ``batch`` (``requests`` all due at time 0) or ``poisson``
  (exponential gaps at ``rate_per_s``, covering the priming period and
  the measured window).
"""
from __future__ import annotations

import dataclasses
import json
import statistics
from pathlib import Path
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    index: int
    due_s: float              # seconds after the schedule starts
    prompt: np.ndarray        # int32 token ids
    max_new: int


def load_mix(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` fixed quantiles of the clipped lognormal, ascending."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def request_count(mix: dict, seconds: float) -> int:
    arr = mix["arrival"]
    if arr["kind"] == "batch":
        return int(arr["requests"])
    if arr["kind"] == "poisson":
        return int(np.ceil(arr["rate_per_s"] * (prime_seconds(mix) + seconds)))
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def prime_seconds(mix: dict) -> float:
    p = mix.get("prime", {"kind": "none"})
    return float(p["seconds"]) if p["kind"] == "seconds" else 0.0


def arrival_times(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    arr = mix["arrival"]
    if arr["kind"] == "batch":
        return np.zeros(n)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / float(arr["rate_per_s"])
    due = np.cumsum(rng.permutation(gaps))
    return due - due[0]


def make_requests(mix: dict, vocab: int, seed: int,
                  seconds: float) -> List[Req]:
    """The mix's requests for one run, in due order."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(0)        # one order for every seed
    n = request_count(mix, seconds)
    due = arrival_times(mix, n, order)
    plen = order.permutation(quantile_lengths(mix["prompt_tokens"], n))
    olen = order.permutation(quantile_lengths(mix["output_tokens"], n))
    return [Req(i, float(max(due[i], 0.0)),
                rng.integers(0, vocab, int(plen[i])).astype(np.int32),
                int(olen[i]))
            for i in range(n)]
