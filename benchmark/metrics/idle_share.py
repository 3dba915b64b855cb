"""1 - device busy / traced window, in percent."""


def idle_pct(ctx, kind):
    red = ctx.get("trace")
    if ctx.get("kind") != kind or not red or red["window_s"] <= 0 or red["devices"] == 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
