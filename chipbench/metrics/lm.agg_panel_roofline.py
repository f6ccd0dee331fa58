"""The Eq. 4 panel kernel (``dystop_aggregate_panel``): the least time the
window's mixes need on this chip over the kernel's summed device time.

The work is Eq. 4's on each round's (k mixed rows, u source rows, P
parameters): 2 k u P FLOPs and 4 (u + k) P bytes of f32 read and written,
from the reference control plane's decisions and the configuration's shapes,
not from how the kernel tiles them."""
import devtrace
import work

KERNEL = "dystop_aggregate_panel"


def read(ctx):
    peaks = ctx["peaks"]
    spent = devtrace.kernel_s(ctx["trace"], KERNEL)
    if peaks is None or spent is None:
        return None
    p = work.lm_param_count(ctx["config"]["model"])
    least = sum(work.roofline_s(*work.eq4_panel_work(k, u, p), peaks)[0]
                for k, u in ctx["session"].mix_shapes())
    return 100.0 * least / spent
