"""Open-loop request traffic: Poisson arrivals at a fixed rate, prompt
and answer lengths log-normal and clipped, greedy decoding.

Every seed gets the SAME schedule: lengths are the log-normal's
quantiles at evenly spaced probabilities and gaps the exponential's,
both shuffled once by the traffic file's ``order_seed``; the run's seed
draws the token ids and nothing else. A saturated engine admits
requests in the order they arrive, and which prompts fall inside a
window decides how much prefill it holds: on the chip, runs that
differed only in that order spread by 5% in tokens per second. So the
order is part of the mix, not of the seed; another realisation of the
same mix is another traffic file with another ``order_seed``.

Parameters (the traffic file): ``rate_rps``; ``prompt_median``,
``prompt_sigma``, ``prompt_min``, ``prompt_max``; ``new_median``,
``new_sigma``, ``new_min``, ``new_max``; ``burst`` requests due at time
zero; ``lead_s`` seconds of traffic before the window opens;
``order_seed`` fixes the order of lengths and gaps.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Request:
    due_s: float  # seconds after traffic starts
    prompt: list  # token ids
    max_new_tokens: int


def _lognormal_quantiles(n: int, median: float, sigma: float,
                         lo: int, hi: int) -> np.ndarray:
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(
        np.rint(median * np.exp(sigma * z)), lo, hi
    ).astype(np.int64)


def schedule_length(params: dict, seconds: float) -> int:
    horizon = float(params["lead_s"]) + seconds + float(
        params.get("tail_s", 2.0)
    )
    return int(params["burst"]) + math.ceil(
        float(params["rate_rps"]) * horizon
    )


def generate(params: dict, *, seed: int, vocab_size: int,
             seconds: float) -> list[Request]:
    n = schedule_length(params, seconds)
    burst = int(params["burst"])
    rng = np.random.default_rng([int(seed), 0x5E27E])
    order = np.random.default_rng([int(params["order_seed"]), 0x0DE2])
    prompts = _lognormal_quantiles(
        n, params["prompt_median"], params["prompt_sigma"],
        params["prompt_min"], params["prompt_max"],
    )
    news = _lognormal_quantiles(
        n, params["new_median"], params["new_sigma"],
        params["new_min"], params["new_max"],
    )
    prompts = prompts[order.permutation(n)]
    news = news[order.permutation(n)]
    k = n - burst
    gaps = -np.log1p(-(np.arange(k) + 0.5) / k) / float(params["rate_rps"])
    due = np.concatenate([
        np.zeros(burst), np.cumsum(gaps[order.permutation(k)])
    ])
    return [
        Request(
            due_s=float(due[i]),
            prompt=rng.integers(0, vocab_size, size=int(prompts[i])).tolist(),
            max_new_tokens=int(news[i]),
        )
        for i in range(n)
    ]
