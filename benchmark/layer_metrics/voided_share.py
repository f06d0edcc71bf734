"""% of the window's sends that a stop of the machine covered and that
left ``attempted`` for it (``run.py`` ``account_for_stops``); 0 is a
value, and the value of every window without a stop of 250 ms."""
LAYER = "load generator"
UNIT = "%"
MOVES = "p50_us"


def read(ctx):
    g = ctx.get("generator") or {}
    if "voided" not in g or not g.get("sent"):
        return None
    return 100.0 * g["voided"]["queries"] / g["sent"]
