"""% of the roll (``roll_s``: the SIGHUP to "rolling upgrade complete")
that its replacements spent filling: the ``fill`` phase of the supervisor's
``binder_shard_roll_phase_seconds`` (a replacement's hello to its *filled*:
zone fill and precompile seed complete, the shard's sockets not read yet),
summed over the shards rolled between the scrapes.  The rest is attach
(spawn to hello) and drain.  Nothing to read on a program without the
histogram, which promotes a replacement before it is filled."""
import roll_spans

LAYER = "mirror and shard mutation log"
UNIT = "%"
MOVES = "p50_us"


def read(ctx):
    fill = roll_spans.phase(ctx, "fill")
    roll_s = (ctx.get("harness") or {}).get("roll_s")
    if fill is None or not roll_s:
        return None
    return 100.0 * fill[0] / roll_s
