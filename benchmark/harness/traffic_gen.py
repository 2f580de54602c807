"""The one general generator of request traffic, driven by a data file.

A traffic mix is parameters, not code: the arrival rate and the
lognormal distributions of prompt and output lengths. The amount of work
in a window is fixed by the parameters, and the seed decides the rest:

- the number of requests is ``round(rate_rps * seconds)`` and arrival
  instants are that many sorted uniform draws, i.e. a Poisson process
  conditioned on its count;
- lengths are the distribution's quantiles at ``(i + 0.5) / n`` (a
  stratified sample), so every run of a cell offers the same prompt
  lengths and the same output lengths; the seed pairs them, orders the
  requests and draws the token ids.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any

import numpy as np


def lognormal_lengths(spec: dict[str, Any], n: int) -> list[int]:
    """``n`` lengths at the evenly spaced quantiles of a lognormal with
    ``median`` and ``sigma``, clipped to ``min``..``max``."""
    mu, sigma = math.log(float(spec["median"])), float(spec["sigma"])
    raw = (math.exp(mu + sigma * NormalDist().inv_cdf((i + 0.5) / n)) for i in range(n))
    return [min(int(spec["max"]), max(int(spec["min"]), int(round(x)))) for x in raw]


def make_requests(traffic: dict[str, Any], seconds: float, seed: int, vocab: int) -> list[dict[str, Any]]:
    """The window's requests, in arrival order: ``due_s``, ``prompt``
    (token ids) and ``max_new_tokens``."""
    n = max(1, int(round(float(traffic["rate_rps"]) * seconds)))
    rs = np.random.RandomState(seed)
    dues = np.sort(rs.uniform(0.0, seconds, n)).tolist()
    prompt_lens = rs.permutation(lognormal_lengths(traffic["prompt_len"], n))
    output_lens = rs.permutation(lognormal_lengths(traffic["output_len"], n))
    return [{"due_s": due, "prompt": rs.randint(0, vocab, int(p)).tolist(), "max_new_tokens": int(o)}
            for due, p, o in zip(dues, prompt_lens, output_lens)]
