"""What a rolled shard's hand-over takes: the ``drain`` phase of the
supervisor's ``binder_shard_roll_phase_seconds`` (SIGTERM to the incumbent,
which stops reading the shard's sockets and serves out what it holds, to
its exit), the mean over the shards rolled between the scrapes.  Nothing to
read on a program without the histogram."""
import roll_spans

LAYER = "mirror and shard mutation log"
UNIT = "ms"
MOVES = "p50_us"


def read(ctx):
    drain = roll_spans.phase(ctx, "drain")
    if drain is None or not drain[1]:
        return None
    return 1e3 * drain[0] / drain[1]
