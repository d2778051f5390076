"""Prompt tokens served from a cached prefix over all prompt tokens of
the window's requests, from the engine's per-request counts (%)."""


def read(rec):
    reqs = rec.window_requests
    total = sum(n for _, n in reqs)
    return 100.0 * sum(m for m, _ in reqs) / total if total else None
