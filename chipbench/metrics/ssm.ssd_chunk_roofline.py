"""The SSD intra-chunk kernel (``dystop_ssd_chunk``): the least time the
window's calls need on this chip over the kernel's summed device time.

A call is one chunk of one layer's forward (``Session.ssd_chunks``: the
activated workers' steps and the evals, from the window's history); its
work is ``work_ssm.ssd_chunk_work``'s, from the configuration's shapes,
not from how the kernel tiles it."""
import devtrace
import work
import work_ssm

KERNEL = "dystop_ssd_chunk"


def read(ctx):
    peaks = ctx["peaks"]
    spent = devtrace.kernel_s(ctx["trace"], KERNEL)
    if peaks is None or spent is None:
        return None
    one = work.roofline_s(*work_ssm.ssd_chunk_work(
        ctx["config"]["model"]), peaks)[0]
    return 100.0 * ctx["session"].ssd_chunks() * one / spent
