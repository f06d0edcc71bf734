"""Share of the UDP answers that left with TC=1 for which a resolve of
the Python lanes rendered the whole set and the encode dropped it
(``binder_truncated_renders`` over ``binder_truncated_responses``): the
rest came from the answer caches, which hold a truncated wire from its
first sight.  Nothing to read on a program without the counter, or in a
window in which no answer left truncated."""
import spans
import stream_spans

LAYER = "Python lanes"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 100.0 * spans.counter(ctx, "binder_truncated_renders") \
        / stream_spans.truncated(ctx)
