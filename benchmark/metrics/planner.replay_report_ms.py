"""Host time per plan in the planner's `apply/replay` + `apply/report`."""


def read(ctx):
    ph = ctx.get("phases", {})
    if ctx.get("kind") != "plan" or "apply/replay" not in ph:
        return None
    s = sum(ph.get(k, [0.0])[0] for k in ("apply/replay", "apply/report"))
    return 1000.0 * s / ctx["ops"]
