"""Time inside the calls that move a query in or an answer out, per
answer: ``recvmmsg`` and ``sendmmsg``, and the stream lane's ``accept``,
``recv``, ``send`` and ``close`` where a cell's clients come back over
TCP (``spans.SOCKET_STAGES``)."""
import spans

LAYER = "kernel socket path"
UNIT = "us"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    if spans.stage(ctx, "udp-send") is None:
        return None
    return spans.per_answer_us(ctx, spans.SOCKET_STAGES)
