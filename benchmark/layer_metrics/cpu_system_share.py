"""System-mode share of the workers' CPU seconds
(``binder_process_cpu_seconds_total{mode="system"}`` over both modes): how
a worker's CPU splits between the program and the sandbox's kernel."""
import loop_spans
import spans

LAYER = "kernel socket path"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 100.0 * loop_spans.cpu_s(ctx, mode="system") / loop_spans.cpu_s(ctx)
