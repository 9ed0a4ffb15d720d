"""The one general generator of traffic. A mix is a data file of
distributions; this turns it and a seed into the inputs of a run.

Every seed gets the SAME multiset of lengths and gaps: a length is the
distribution's quantile at (i + 0.5) / n, not a random draw, so the seed
cannot change the amount of work. Token ids are uniform draws from the
seed. Documents come in another order for every seed. A serving mix's
schedule (which request is due when, with which lengths) is drawn from
the MIX's own ``schedule_seed`` and is the same in every run: on the
chip, six orders of one multiset spread the 95th percentile of the time
to first token by 19 % and the tokens per second above the knee by 5 %
(PERF.md, PR 23), because the order decides which requests end inside
the window and how many prefills interrupt the decoding, and no bound
the contract admits (10 % at most) holds a spread of 19 %.

What that costs: a serving cell replays ONE sample path. Its tails are
the tails of that path, good for telling two versions of the program
apart on the same requests, and not an estimate of the deployment's
tails; the spread between runs is the noise of one schedule, not of
schedules. Another order is another mix file with another
``schedule_seed``: a cell that a later PR adds with data alone."""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile_values(spec: dict, n: int) -> np.ndarray:
    """n values at the even quantiles of the distribution in ``spec``."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
        vals = np.clip(np.round(vals), spec["min"], spec["max"])
        return vals.astype(np.int64)
    if spec["dist"] == "exponential":
        return -np.log1p(-u) / spec["rate_per_s"]
    raise ValueError(f"unknown distribution {spec['dist']!r}")


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def documents(mix: dict, vocab: int, seed: int, min_tokens: int) -> list:
    """Documents of the mix's lengths, shuffled anew each pass over the
    set, until they hold ``min_tokens`` tokens."""
    lengths = quantile_values(mix["documents"]["length"], mix["documents"]["count"])
    rng = _rng(seed, 1)
    docs, total = [], 0
    while total < min_tokens:
        for n in rng.permutation(lengths):
            docs.append(rng.integers(1, vocab, size=int(n), dtype=np.int32))
            total += int(n)
    return docs


def requests(mix: dict, vocab: int, seed: int, seconds: float) -> list[dict]:
    """The requests due in a window of ``seconds``: due time, prompt,
    output length and sampling. The gaps are the exponential's even
    quantiles, rescaled so that they fill the window."""
    rate = mix["arrivals"]["rate_per_s"]
    n = max(int(round(rate * seconds)), 1)
    tokens_rng = _rng(seed, 2)
    rng = _rng(mix["schedule_seed"], 4)
    gaps = rng.permutation(quantile_values(mix["arrivals"], n))
    # every gap is used, the first as the wait before the first request;
    # the last request is due one mean gap before the window closes
    due = np.cumsum(gaps) * (seconds / (gaps.sum() + gaps.mean()))
    prompts = rng.permutation(quantile_values(mix["prompt"], n))
    outputs = rng.permutation(quantile_values(mix["output"], n))
    n_greedy = int(math.ceil(mix.get("greedy_share", 0.0) * n))
    greedy = np.zeros(n, bool)
    greedy[rng.permutation(n)[:n_greedy]] = True
    out = []
    for i in range(n):
        out.append({
            "id": i,
            "due_s": float(due[i]),
            "prompt": tokens_rng.integers(1, vocab, size=int(prompts[i])).tolist(),
            "max_tokens": int(outputs[i]),
            "greedy": bool(greedy[i]),
        })
    return out
