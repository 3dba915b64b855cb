"""Probes per plan: entries of the program's `sweep/probe` phase."""


def read(ctx):
    rec = ctx.get("phases", {}).get("sweep/probe")
    if ctx.get("kind") != "plan" or rec is None:
        return None
    return rec[1] / ctx["ops"]
