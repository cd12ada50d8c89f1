"""The one traffic generator: a mix's parameters (``traffic/<mix>.json``)
and a seed in, the list of requests with their due times out.

Every seed sends the same work: the lengths are the quantiles of the
mix's distribution at (i + 1/2)/N and the gaps between arrivals the
quantiles of an exponential at the mix's rate, each list in one fixed
shuffled order for every mix and seed. Within a window of a minute the
order decides how much work falls inside it (how long the queue grows
behind a burst of long prompts, which retirements a saturated window
holds), so an order drawn from the seed would make the spread between
runs measure the draw, not the system. The seed draws the token ids,
uniform over the vocabulary, as it draws the weights. Nothing here
imports JAX or the program."""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    rid: int
    due_s: float              # seconds after the loop starts
    tokens: np.ndarray        # (prompt_len,) int32
    max_new_tokens: int


def _quantiles(dist: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        from statistics import NormalDist

        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif dist["dist"] == "fixed":
        v = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def request_count(mix: dict, seconds: float) -> int:
    """Requests planned for a window of ``seconds``: the initial burst
    plus the arrivals due within the window and the mix's allowance for
    the window's late opening."""
    arr = mix["arrivals"]
    span = seconds + float(arr.get("open_allowance_s", 0.0))
    return int(arr.get("initial_burst", 0)) + math.ceil(arr["rate_rps"] * span)


def generate(mix: dict, vocab_size: int, seed: int,
             seconds: float) -> List[Planned]:
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    n = request_count(mix, seconds)
    burst = int(arr.get("initial_burst", 0))
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(0)
    prompts = order.permutation(_quantiles(mix["prompt"], n))
    outputs = order.permutation(_quantiles(mix["output"], n))
    u = (np.arange(n - burst) + 0.5) / max(n - burst, 1)
    gaps = -np.log1p(-u) / float(arr["rate_rps"])
    gaps = order.permutation(gaps)
    due = np.concatenate([np.zeros(burst), np.cumsum(gaps)])
    return [Planned(rid=i, due_s=float(due[i]),
                    tokens=rng.integers(0, vocab_size, int(prompts[i]),
                                        dtype=np.int32),
                    max_new_tokens=int(outputs[i]))
            for i in range(n)]
