"""Closed-loop sessions over long documents with context caching.

``clients`` callers each own ONE document (nothing shared between clients);
a request is its client's document + a fresh question, answered with a
short answer, and the client asks the next when the answer ends, with no
think time.  Document lengths are the ``clients`` stratified quantiles of a
uniform distribution, rounded to whole pages and dealt to the clients in a
seeded order.  Every seed gets the SAME multiset of (question length,
answer length) pairs — ``closed_loop.py``'s rules: stratified quantiles of
the two clipped lognormals, dealt in blocks of ``block`` in another order
with other token ids — and the first ``clients`` answers are cut to a spread
of fractions so that the pool starts the window out of step.

The entry submits each document bare (one new token) during set-up, so the
server's chunked prefill builds its cache and the prefix index registers
its end; a request in the window is then a prefix hit on the document and
one short chunk.  A request's prompt is put together when it is submitted
(``documents[client]`` + ``question``): which client asks which question is
decided by who finishes first.
"""
import numpy as np

from chipbench.generators.closed_loop import _quantile_lengths


def make(traffic, seed, vocab_size):
    """``{"mode": "closed", "clients": n, "documents": [int32 arrays],
    "requests": [{"question": int32 array, "max_new": int}, ...]}``."""
    rng = np.random.default_rng(int(seed))
    clients = int(traffic["clients"])
    d = traffic["doc_len"]
    unit = int(d.get("round_to", 1))
    lengths = [int(round((d["min"] + (j + 0.5) / clients
                          * (d["max"] - d["min"])) / unit)) * unit
               for j in range(clients)]
    documents = [rng.integers(0, vocab_size, int(n), dtype=np.int32)
                 for n in rng.permutation(lengths)]
    block, total = int(traffic["block"]), int(traffic["requests"])
    q_q = _quantile_lengths(traffic["question_len"], block)
    a_q = _quantile_lengths(traffic["answer_len"], block)
    requests = []
    while len(requests) < total:
        for q, a in zip(rng.permutation(q_q), rng.permutation(a_q)):
            requests.append({
                "question": rng.integers(0, vocab_size, int(q),
                                         dtype=np.int32),
                "max_new": int(a)})
    if traffic.get("stagger_first"):
        for i, frac in enumerate(rng.permutation(clients)):
            r = requests[i]
            r["max_new"] = max(int(traffic["answer_len"]["min"]),
                               int(r["max_new"] * (frac + 0.5) / clients))
    longest = max(lengths) + traffic["question_len"]["max"] \
        + traffic["answer_len"]["max"]
    if longest > traffic["max_total"]:
        raise ValueError(f"a session can reach {longest} tokens, past "
                         f"max_total {traffic['max_total']}")
    return {"mode": "closed", "clients": clients, "documents": documents,
            "requests": requests[:total]}
