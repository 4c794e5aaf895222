"""Persistent compile-cache hits by the start of the window
(``compile_cache.stats()["hits"]``): why a set-up was short or long."""


def read(run):
    stats = run.get("cache_at_window")
    return None if stats is None else stats["hits"]
