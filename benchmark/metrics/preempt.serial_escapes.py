"""Preemptions that took the serial cycle over the window: the program
counter `preempt_serial_escapes_total`. None where the program has no
such counter."""


def read(ctx):
    if ctx.get("kind") != "preempt":
        return None
    v = ctx.get("counters", {}).get("preempt_serial_escapes_total")
    return None if v is None else float(v)
