"""Closed-loop request traffic from a file of parameters.

``clients`` callers each submit, stream the answer to its end and submit
the next with no think time.  Every seed gets the SAME multiset of
(prompt length, output length) pairs — stratified quantiles of the two
clipped lognormals, dealt in blocks of ``block`` requests so that any
stretch of consecutive requests holds the whole distribution — in another
order and with other token ids; so two seeds differ in what is said, not
in how much work it is.  The first ``clients`` requests have their outputs
cut to a spread of fractions (``stagger_first``) so that the pool starts
the window out of step, as a pool in service is.
"""
import statistics

import numpy as np


def _quantile_lengths(spec, n):
    nd = statistics.NormalDist()
    out = []
    for j in range(n):
        z = nd.inv_cdf((j + 0.5) / n)
        x = spec["median"] * np.exp(spec["sigma"] * z)
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return np.asarray(out, dtype=np.int64)


def make(traffic, seed, vocab_size):
    """``{"mode": "closed", "clients": n, "requests": [{"prompt": int32
    array, "max_new": int}, ...]}``; requests are taken in list order."""
    rng = np.random.default_rng(int(seed))
    block, total = int(traffic["block"]), int(traffic["requests"])
    p_q = _quantile_lengths(traffic["prompt_len"], block)
    o_q = _quantile_lengths(traffic["output_len"], block)
    requests = []
    while len(requests) < total:
        for p, o in zip(rng.permutation(p_q), rng.permutation(o_q)):
            o = int(min(o, traffic["max_total"] - p))
            requests.append({
                "prompt": rng.integers(0, vocab_size, int(p),
                                       dtype=np.int32),
                "max_new": o})
    clients = int(traffic["clients"])
    if traffic.get("stagger_first"):
        for i, frac in enumerate(rng.permutation(clients)):
            r = requests[i]
            r["max_new"] = max(int(traffic["output_len"]["min"]),
                               int(r["max_new"] * (frac + 0.5) / clients))
    return {"mode": "closed", "clients": clients,
            "requests": requests[:total]}
