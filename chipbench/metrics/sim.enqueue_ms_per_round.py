"""Host milliseconds a round spends inside the jitted step calls, from
entry to return (``enqueue_wall_s``): argument handling and dispatch."""


def read(ctx):
    h = ctx["session"].history
    spent = getattr(h, "enqueue_wall_s", None)
    if spent is None or not h.round_active:
        return None
    return 1e3 * spent / len(h.round_active)
