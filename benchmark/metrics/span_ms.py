"""Host time per operation in spans the program opens itself
(`utils.trace.phase`), summed over the window. None in the other kind
of cell, and where the program opens none of them (a program older
than the spans)."""


def span_ms(ctx, kind, names):
    ph = ctx.get("phases", {})
    if ctx.get("kind") != kind or not any(n in ph for n in names):
        return None
    return 1000.0 * sum(ph[n][0] for n in names if n in ph) / ctx["ops"]
