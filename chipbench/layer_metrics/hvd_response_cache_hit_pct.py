"""Share of the window's response-cache lookups that hit
(``hvd.metrics()["cache"]``), in percent. ``None`` where nothing was
looked up: at size 1 the controller answers without the cache."""


def read(ctx):
    before, after = ctx.counters
    if not after or "cache" not in after:
        return None
    hits, misses = (after["cache"][k] - before["cache"][k]
                    for k in ("hits", "misses"))
    return 100.0 * hits / (hits + misses) if hits + misses else None
