"""Kernel crossings the workers made per answer: ``select``, ``recvmmsg``
(empty-handed ones too), ``sendmmsg``, writes of the query log (the native
ring's block with the Python lanes' lines behind it) and the stream lane's
``accept``, ``recv``, ``send`` and ``close``, by the counts of their spans
(``spans.SYSCALL_STAGES``)."""
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
