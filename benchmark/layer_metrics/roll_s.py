"""A roll of every shard, from the SIGHUP's delivery to the supervisor's
"rolling upgrade complete" line, by the log record's arrival on the
harness's clock: one replacement after another spawned, attached from the
snapshot, joined to the reuseport group and ready, the incumbent drained.
Nothing to read where the workload delivers no event or the roll did not
end."""
LAYER = "mirror and shard mutation log"
UNIT = "s"
MOVES = "p50_us"


def read(ctx):
    return (ctx.get("harness") or {}).get("roll_s")
