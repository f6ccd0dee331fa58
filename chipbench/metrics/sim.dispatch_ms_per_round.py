"""Host milliseconds a round spends packing and staging its dispatch
(``pack_wall_s`` + ``stage_wall_s``)."""


def read(ctx):
    if not ctx["units"]:
        return None
    s = ctx["spans"]
    return 1e3 * (s["pack_wall_s"] + s["stage_wall_s"]) / ctx["units"]
