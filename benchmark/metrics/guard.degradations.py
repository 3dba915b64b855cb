"""Guard-ladder degradations over the window (runtime/guard.degradations
after every plan); anything but 0 means a rung was skipped."""


def read(ctx):
    if ctx.get("kind") != "plan":
        return None
    return float(ctx["degradations"])
