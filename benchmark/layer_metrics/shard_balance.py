"""How evenly the kernel's reuseport hash spread the load over the workers:
least over greatest share of the requests."""
import stats

LAYER = "kernel socket path"
UNIT = "ratio"
MOVES = "p50_us"


def read(ctx):
    return stats.shard_balance(ctx)
