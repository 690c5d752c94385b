"""Entries this run added to the persistent compile cache
(``utils.compile_cache.cache_stats()`` after minus before): 0 on a warm
run, or set-up compiled something the cache should have held."""

UNIT = "count"


def read(records, trace, cell):
    return records.counters.get("cache_entries_added")
