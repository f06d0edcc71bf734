"""Arithmetic shared by the readers of the balancer front end
(``balancer_cpu_share``, ``balancer_us_per_query``, ``balancer_cache_share``,
``direct_return_share``, ``backend_balance``): the balancer's own counters,
as its stats socket ``sockets/.balancer.stats`` gives them
(``docs/balancer-protocol.md``), and its process's CPU seconds by ``/proc``,
at the two scrapes of a traced run.  Every function returns ``None`` where a
scrape holds no balancer (a supervisor topology, a ``ctx`` without scrapes),
and the readers pass that on."""


def fronts(ctx):
    """The balancer's part of the two scrapes (``stats``, ``pid``,
    ``cpu_s``), or None.  A balancer that was replaced between them has
    counters from zero and is read as none."""
    try:
        before, after = ctx["before"]["balancer"], ctx["after"]["balancer"]
    except (KeyError, TypeError):
        return None
    if before["pid"] != after["pid"]:
        return None
    return before, after


def grew(ctx, *names):
    """How much the sum of some of the balancer's counters grew between
    the scrapes."""
    both = fronts(ctx)
    if both is None:
        return None
    before, after = both
    return sum(after["stats"][n] - before["stats"][n] for n in names)


def queries(ctx):
    """Queries the balancer took off its clients' sockets."""
    n = grew(ctx, "udp_queries", "tcp_queries")
    return n if n and n > 0 else None


def cpu_s(ctx):
    """CPU seconds, both modes, of the balancer's process."""
    both = fronts(ctx)
    if both is None or None in (both[0]["cpu_s"], both[1]["cpu_s"]):
        return None
    return both[1]["cpu_s"] - both[0]["cpu_s"]


def forwarded(ctx):
    """Per backend, the queries the balancer handed it between the scrapes
    (by the backend's socket path: an id is a slot and may be given anew)."""
    both = fronts(ctx)
    if both is None:
        return None
    was = {b["path"]: b["forwarded"] for b in both[0]["stats"]["backends"]}
    return [b["forwarded"] - was.get(b["path"], 0)
            for b in both[1]["stats"]["backends"]]
