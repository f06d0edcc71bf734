"""Arithmetic shared by the readers of what a roll did (``roll_fill_share``,
``roll_drain_ms``, ``roll_unserved_queries``): the supervisor's
``binder_shard_roll_*`` families between the two scrapes of a traced run.
The supervisor keeps the account, since a rolled worker's counters die with
its pid."""
import stats

FAMILY = "binder_shard_roll_phase_seconds"


def supervisor_texts(ctx):
    """The supervisor's ``/metrics`` at the two scrapes, or None."""
    try:
        return (ctx["before"]["supervisor"]["metrics"],
                ctx["after"]["supervisor"]["metrics"])
    except (KeyError, TypeError):
        return None


def counter(ctx, name):
    """How much a counter of the supervisor's grew between the scrapes;
    None where a scrape is missing or the program has no such counter."""
    texts = supervisor_texts(ctx)
    if texts is None or not stats.samples(texts[1], name):
        return None
    return stats.total(texts[1], name) - stats.total(texts[0], name)


def phase(ctx, name):
    """``(seconds, shards)`` the supervisor observed for one phase between
    the scrapes; None where a scrape is missing or the program has no such
    histogram."""
    texts = supervisor_texts(ctx)
    if texts is None:
        return None
    before, after = texts

    def part(text, suffix):
        hits = [v for labels, v in stats.samples(text, FAMILY + suffix)
                if labels.get("phase") == name]
        return sum(hits) if hits else None

    seconds, shards = part(after, "_sum"), part(after, "_count")
    if seconds is None or shards is None:
        return None
    return (seconds - (part(before, "_sum") or 0.0),
            shards - (part(before, "_count") or 0.0))
