"""Host milliseconds a round spends in the planner (``plan_wall_s``)."""


def read(ctx):
    if not ctx["units"]:
        return None
    return 1e3 * ctx["spans"]["plan_wall_s"] / ctx["units"]
