"""Arithmetic shared by the per-layer readers of what a service zone adds
to a worker's second: the stream lane's four leaf spans of the time
ledger (``tcp-accept``, ``tcp-recv``, ``tcp-send``, ``tcp-close``: one
kernel crossing of a TCP leg each), the count of UDP answers that left
truncated and so caused a leg, and the per-query stage of a set rendered
at query time because it is too large to precompile (``lazy-render``).

Built on ``spans.py``: deltas between the two scrapes of a traced run,
summed over the workers, ``None`` where the program exports no such span
or counter (a program older than these), which the readers pass on.
"""
import spans

LAZY_STAGE = "lazy-render"


def positive(value):
    return value if value and value > 0 else None


def legs(ctx):
    """Connections the stream lane accepted: in a cell whose clients
    retry a truncated answer over a connection of its own, its legs."""
    return positive(spans.counter(ctx, "binder_tcp_accepts"))


def udp_answers(ctx):
    """Datagrams the batched sends put on the wire."""
    return positive(spans.counter(ctx, "binder_udp_datagrams", dir="out"))


def stream_answers(ctx):
    """Frames served on connections that sent one burst and left
    (``fast_serves``): every answer of a one-shot leg."""
    return spans.counter(ctx, "binder_tcp_fast_serves")


def truncated(ctx):
    """UDP answers that left with TC=1, over all query types."""
    return spans.counter(ctx, "binder_truncated_responses")


def tcp(ctx, part="sum"):
    """Seconds inside the four spans (``part="sum"``) or their
    observations (``"count"``); None unless the program has all four
    (a part of them would read as a cheaper leg)."""
    parts = [spans.stage(ctx, name, part) for name in spans.TCP_STAGES]
    return None if None in parts else sum(parts)


def busy_s(ctx):
    idle, wall = spans.stage(ctx, "loop-idle"), spans.wall_s(ctx)
    if idle is None or not wall or wall <= idle:
        return None
    return wall - idle
