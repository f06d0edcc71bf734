"""Share of the window's answers that came to questions of a mix entry
whose every template expects NOTIMP, by the counts of the generator's
per-entry latency histograms and the rcodes the traffic's templates hold
(``ctx["mix_rcodes"]``).  A property of the traffic, not of the program:
the control of a mix with a declined type in it, 50 where A and AAAA go
out one to one; neither direction is better.  Nothing to read where the
mix holds no declined type."""
import dnswire

LAYER = "load generator"
UNIT = "%"
MOVES = "p50_us"


def read(ctx):
    by_entry = (ctx.get("generator") or {}).get("latency_ns_by_entry")
    rcodes = ctx.get("mix_rcodes")
    if not by_entry or not rcodes:
        return None
    answers = [sum(count for _bucket, count in hist["latency_ns"])
               for hist in by_entry]
    declined = sum(n for n, held in zip(answers, rcodes)
                   if held == [dnswire.NOTIMP])
    if not declined:
        return None
    return 100.0 * declined / sum(answers)
