"""Rounds per device dispatch (the window's rounds over
``counts["dispatches"]``): how many rounds a scan chunk carries."""


def read(ctx):
    h = ctx["session"].history
    counts = getattr(h, "counts", None)
    if not counts or not counts.get("dispatches"):
        return None
    return len(h.round_active) / counts["dispatches"]
