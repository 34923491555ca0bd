"""The one general traffic generator.  A traffic mix is a data file of
parameters (`workloads/<cell>.json`, key `traffic_mix`); this module turns
it and `--seed` into requests.

The mix's own `shape_seed` draws the request sizes and, in an open loop, the
instants they are due: every run of the cell, whatever its `--seed`, offers
exactly that schedule.  `--seed` draws the token ids (and the weights), so it
never changes how much work a run holds or when it arrives.  (A tail over a
hundred requests swings several-fold with the order of the sizes alone.)

    "arrivals":   {"process": "poisson", "rate_rps": 5.0, "ramp_s": 5}
                | {"process": "closed", "clients": 64, "ramp_s": 8,
                   "max_rps": 20}
    "prompt_len": {"dist": "normal", "mean": 550, "stddev": 150,
                   "min": 16, "max": 832}
                | {"dist": "uniform", "min": 512, "max": 960}
                | {"dist": "const", "value": 128}
    "output_len": the same forms

`ramp_s` seconds of the same traffic run before the window opens, so that the
window starts on a system in its steady state and not on an empty one; the
ramp's requests have a negative `due` (open loop) and count in no tail.
"""
from __future__ import annotations

import math

import numpy as np


def _lengths(spec, n, rng):
    dist = spec["dist"]
    if dist == "const":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "uniform":
        return rng.integers(int(spec["min"]), int(spec["max"]) + 1, size=n)
    if dist == "normal":
        x = rng.normal(spec["mean"], spec["stddev"], size=n)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError("unknown length distribution %r" % (dist,))


def seed_rng(seed, salt):
    """`--seed` may be any whole number a little over 2**31."""
    seed = int(seed)
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, salt])


def generate(mix, seed, seconds, vocab):
    """[{"due": seconds from the window's start (negative in the ramp; None
         in a closed loop, where a client sends when its last reply came),
         "prompt": [ids], "max_new": n}], in submission order."""
    arr = mix["arrivals"]
    ramp = float(arr.get("ramp_s", 0.0))
    span = float(seconds) + ramp
    shape_rng = np.random.default_rng(int(mix["shape_seed"]))
    if arr["process"] == "poisson":
        # a Poisson process given its count: rate x span arrivals at
        # independent uniform instants, so that the rate offered is the
        # rate stated
        n = int(round(arr["rate_rps"] * span))
        due = np.sort(shape_rng.uniform(0.0, span, size=n)) - ramp
    elif arr["process"] == "closed":
        # an upper bound on what the clients can turn over
        n = int(arr["clients"] + math.ceil(arr["max_rps"] * span))
        due = None
    else:
        raise ValueError("unknown arrival process %r" % (arr["process"],))
    prompt_len = _lengths(mix["prompt_len"], n, shape_rng)
    output_len = _lengths(mix["output_len"], n, shape_rng)
    rng = seed_rng(seed, 0x7AFF1C)
    return [{"due": None if due is None else float(due[i]),
             "prompt": rng.integers(0, vocab, size=int(prompt_len[i]))
             .astype(np.int32).tolist(),
             "max_new": int(output_len[i])} for i in range(n)]
