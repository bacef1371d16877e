"""The one general generator of request traffic, driven by a traffic file.

A decode mix gives, for prompts and for replies, a log-normal length
distribution (`median`, `sigma`, clipped to `min`..`max`).  Every seed gets
the same set of lengths: `cycle` evenly spaced quantiles of each distribution,
repeated cycle after cycle; the seed only decides the order inside a cycle,
the pairing of prompts with reply lengths, and the token ids (uniform over
the vocabulary).  So two seeds do the same work in another order.
"""

import math
import statistics

import numpy as np


def length_set(spec, n):
    """`n` lengths at the quantiles (i + 1/2) / n of the clipped log-normal."""
    normal = statistics.NormalDist()
    out = []
    for i in range(n):
        z = normal.inv_cdf((i + 0.5) / n)
        x = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def decode_requests(traffic, seed, vocab_size):
    """The request list of one run: dicts of `prompt` (token ids) and `max_new`."""
    rng = np.random.default_rng([int(seed), 0xDEC0DE])
    n = int(traffic["cycle"])
    prompts, replies = length_set(traffic["prompt_tokens"], n), length_set(traffic["max_new"], n)
    out = []
    while len(out) < int(traffic["requests"]):
        for p, m in zip(rng.permutation(prompts), rng.permutation(replies)):
            out.append({"prompt": rng.integers(0, vocab_size, size=int(p)).tolist(),
                        "max_new": int(m)})
    return out[: int(traffic["requests"])]
