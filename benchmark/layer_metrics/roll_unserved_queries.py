"""Queries that rolled incumbents still held when their drain deadline
came (the supervisor's ``binder_shard_roll_unserved_total``, from each
leaving worker's last frame; a rolled worker's own counters die with its
pid), between the scrapes.  0 is the promise, and a value.  Nothing to read
where no shard was rolled between the scrapes, or on a program without the
counter, where the number is a log line."""
import roll_spans

LAYER = "mirror and shard mutation log"
UNIT = "count"
MOVES = "p50_us"


def read(ctx):
    drained = roll_spans.phase(ctx, "drain")
    if drained is None or not drained[1]:
        return None
    return roll_spans.counter(ctx, "binder_shard_roll_unserved_total")
