"""Spawn of the supervisor to its "service started" announce: the owner's
mirror build, the shard snapshots and attaches."""
LAYER = "mirror and shard mutation log"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    return ctx["harness"]["ready_s"]
