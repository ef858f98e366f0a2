"""Prompt tokens served from cached prefix pages over all prompt tokens
admitted in the window (registry counters, window delta), percent."""


def read(obs):
    c = obs.get("counters")
    if not c:
        return None
    hit = c["prefix_cache_hit_tokens_total"]
    total = hit + c["prefix_cache_miss_tokens_total"]
    return 100.0 * hit / total if total else None
