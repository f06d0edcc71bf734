"""Time writing the query log per answer: the native ring's drains
(``log-write``) and the Python lanes' lines (``log-line``: format, write,
flush)."""
import spans

LAYER = "query log"
UNIT = "us"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return spans.per_answer_us(ctx, ("log-write", "log-line"))
