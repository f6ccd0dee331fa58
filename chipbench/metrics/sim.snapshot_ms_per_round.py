"""Host milliseconds a round spends writing fleet snapshots
(``snapshot_wall_s``, the ``snapshot`` span less the drain inside it)."""


def read(ctx):
    h = ctx["session"].history
    spent = getattr(h, "snapshot_wall_s", None)
    if spent is None or not h.round_active:
        return None
    return 1e3 * spent / len(h.round_active)
