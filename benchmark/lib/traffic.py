"""The one general traffic generator: a traffic file's parameters and a seed
give the requests and their arrival times. Standard library only, so that the
load generator's process never loads JAX.

Every seed gets the same work. The sizes are the distribution's evenly
spaced quantiles, paired and ordered by the traffic file's `pool_seed`: a
window holds a few dozen long requests, so another order of them would be
another amount of work (three runs of the longdoc mix with the order drawn from
the seed read 31, 40 and 41 tokens/s; PERF.md section 6). The seed draws the
token ids (and the harness the weights), and shuffles the gaps between the
arrivals of an open loop, whose set is the same for every seed.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def stratified_lengths(dist: dict, n: int) -> list:
    """n whole lengths at the evenly spaced quantiles of a clipped lognormal
    ({"median", "sigma", "min", "max"})."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = dist["median"] * math.exp(dist["sigma"] * z)
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def request_pool(traffic: dict, seed: int, vocab: int) -> list:
    """The cell's pool of requests, cycled through in order: the pairing of
    prompt and answer lengths and their order are the traffic file's
    (`pool_seed`), the token ids are the run's."""
    n = traffic["pool"]
    prompts = stratified_lengths(traffic["prompt_len"], n)
    answers = stratified_lengths(traffic["answer_len"], n)
    order = random.Random(traffic["pool_seed"])
    order.shuffle(answers)
    pairs = [(p, min(a, traffic["total_max"] - p))
             for p, a in zip(prompts, answers)]
    order.shuffle(pairs)
    rng = random.Random(seed)
    return [{"prompt": [rng.randrange(vocab) for _ in range(p)],
             "max_new_tokens": a, "temperature": traffic["temperature"]}
            for p, a in pairs]


def arrival_times(traffic: dict, seed: int, horizon_s: float) -> list:
    """Open loop: seconds from the generator's start at which each request
    is due, up to the horizon. Poisson arrivals at `rate_per_s`: a block of
    stratified exponential gaps, shuffled by the seed and repeated (each
    repeat shuffled anew), so every seed offers the same load."""
    rate = traffic["rate_per_s"]
    block = traffic.get("gap_block", 64)
    gaps = [-math.log(1.0 - (i + 0.5) / block) / rate for i in range(block)]
    norm = block / rate / sum(gaps)  # the block's mean gap is exactly 1/rate
    gaps = [g * norm for g in gaps]
    rng = random.Random(seed + 1)
    out, t = [], 0.0
    while True:
        rng.shuffle(gaps)
        for g in gaps:
            t += g
            if t >= horizon_s:
                return out
            out.append(t)
