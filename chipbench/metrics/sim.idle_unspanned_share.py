"""Share of the device's idle time, over the window call's extent, in which
no ``dystop/`` span was open on the host: idle time no phase explains."""
import hostspans


def read(ctx):
    spanned = hostspans.idle_share(ctx["trace"])
    return None if spanned is None else 100.0 - spanned
