"""How long the machine stood still inside the window, by the generator's
own clock: the sum, cut to the window, of every stop ``run.py`` named
(every sender thread at once, 50 ms or more; the short ones too, which
cover no query).  ``sandbox_freeze_ms`` is the workers' view of the same
instants and exists in a traced run only; 0 is a value."""
LAYER = "load generator"
UNIT = "ms"
MOVES = "p50_us"


def read(ctx):
    g = ctx.get("generator") or {}
    if "stops" not in g:
        return None
    window = float(g["window_s"])
    return 1e3 * sum(max(0.0, min(start + length, window) - max(start, 0.0))
                     for start, length in g["stops"])
