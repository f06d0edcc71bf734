"""Announce to the last worker settled: the background precompile seed and
native zone fill, which hold each worker's loop while they run."""
LAYER = "precompile and zone fill"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    return ctx["harness"]["seed_s"]
