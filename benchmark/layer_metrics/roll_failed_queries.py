"""Queries the roll cost: the generator's failures among the queries *due*
from the SIGHUP on (every segment of the window but the first: while the
roll ran, and after it), after the queries that a stop of the machine
covers have left the counts (``run.py`` ``account_for_stops``).  The
program's promise for a roll is 0, and 0 is a value.  Nothing to read where
the workload delivers no event or the window is not cut."""
LAYER = "load generator"
UNIT = "count"
MOVES = "p50_us"


def read(ctx):
    segments = (ctx.get("generator") or {}).get("latency_ns_by_segment") or []
    if not ctx.get("events") or len(segments) < 2:
        return None
    return sum(segment["failed"] for segment in segments[1:])
