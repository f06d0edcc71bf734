"""Kernel crossings the workers made per answer: ``select``, ``recvmmsg``
(empty-handed ones too), ``sendmmsg``, writes of the native log ring and of
Python-lane log lines, by the counts of their spans."""
import spans

LAYER = "kernel socket path"
UNIT = "count"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    if spans.stage(ctx, "loop-idle", "count") is None:
        return None
    return (spans.stages(ctx, spans.SYSCALL_STAGES, "count")
            / spans.answers(ctx))
