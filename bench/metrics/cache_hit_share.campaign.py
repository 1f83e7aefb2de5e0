"""cache.hits / (cache.hits + cache.misses) over the window."""


def read(run):
    hits, misses = run.counters["cache.hits"], run.counters["cache.misses"]
    return 100.0 * hits / (hits + misses) if hits + misses else None
