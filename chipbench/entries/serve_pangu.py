"""The serving entry of the pangu_ultra_moe configuration: the model behind
``serve.DecodeServer`` on the scheduler's own thread, driven by sessions over
each client's own cached long document (``generators/doc_sessions.py``).

The run IS ``entries/serve_dots3.py``'s: the same set-up (every client's
document submitted bare with one new token, so that the server's chunked
prefill builds its cache and the prefix index registers its end; then a few
whole sessions, so that every executable the window can reach has run), the
same session loop, window, counters and ``correct`` (the served-token gap to
the plain reference over a seeded sample of the finished requests, the longest
among them; limit from chip readings of the program and of the int8
control, the configuration's ``limits_why``).  That run names its model
family by two modules, ``dots3`` (``reference_config``, ``build``,
``shapes``, ``load_seeded``, ``seeded_weights``) and ``reference_dots3``
(``served_gaps``); ``chipbench/pangu.py`` and ``reference_pangu.py`` have
the same functions, so this entry runs it with them in those places and adds
the one reading that run lacks, the first-token tail.
"""
from unittest import mock

from chipbench import harness, pangu, reference_pangu
from chipbench.entries import serve_dots3


def run(ctx):
    with mock.patch.multiple(serve_dots3, dots3=pangu,
                             reference_dots3=reference_pangu):
        out = serve_dots3.run(ctx)
    w = out["window"]
    ttft = [((r["times"][0] if r["times"] else w["t_end"]) - r["submit"])
            * 1e3 for r in out["records"]
            if w["t_open"] <= r["submit"] < w["t_close"]]
    out["end_to_end"]["ttft_p95_ms"] = \
        harness.percentile(ttft, 95) if ttft else None
    return out
