"""Prompt tokens the prefix cache served over prompt tokens admitted in the
window (``DecodeServer.stats()``)."""


def read(run):
    c = run["counters"]
    if not c.get("prompt_tokens"):
        return None
    return 100.0 * c["prompt_tokens_cached"] / c["prompt_tokens"]
