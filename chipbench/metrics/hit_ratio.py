"""Share (%) of the window's record accesses served from local frames."""


def read(rec):
    n = rec.stats["hits"] + rec.stats["misses"]
    return 100.0 * rec.stats["hits"] / n if n else None
