"""Percent of queries the decoded-region cache answered, from the server's
hit and miss counters over the window."""


def read(obs):
    hits = obs.counters.get("region_cache_hits", 0)
    total = hits + obs.counters.get("region_cache_misses", 0)
    return 100.0 * hits / total if total else None
