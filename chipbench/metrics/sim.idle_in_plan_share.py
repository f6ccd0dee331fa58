"""Share of the device's idle time, over the window call's extent, in which
the host was in the planner (a ``dystop/plan`` span was open)."""
import hostspans


def read(ctx):
    return hostspans.idle_share(ctx["trace"], {"plan"})
