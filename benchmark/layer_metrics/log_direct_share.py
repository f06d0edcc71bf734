"""Share of the Python lanes' query-log lines that were rendered straight
to bytes and written with the native ring's drain
(``binder_query_log_lines{path="direct"}``), the rest having gone through
``logging`` (``path="logging"``: the slow-query warning, any logger that
is not a JSON stream).  Nothing to read on a program without the counter,
or in a window in which the Python lanes logged no line."""
import spans

LAYER = "query log"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    direct = spans.counter(ctx, "binder_query_log_lines", path="direct")
    logged = spans.counter(ctx, "binder_query_log_lines", path="logging")
    return 100.0 * direct / (direct + logged)
