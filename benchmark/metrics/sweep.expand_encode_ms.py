"""Host time per plan in the sweep's `sweep/expand` + `sweep/encode`."""


def read(ctx):
    ph = ctx.get("phases", {})
    if ctx.get("kind") != "plan" or "sweep/expand" not in ph:
        return None
    s = sum(ph.get(k, [0.0])[0] for k in ("sweep/expand", "sweep/encode"))
    return 1000.0 * s / ctx["ops"]
