"""The Eq. 4 panel kernel (``dystop_aggregate_panel``) on a Mamba-2 fleet:
the least time the window's mixes need on this chip over the kernel's
summed device time, reckoned as ``lm.agg_panel_roofline`` reckons it, at
the configuration's P (``work_ssm.ssm_param_count``)."""
import devtrace
import work
import work_ssm

KERNEL = "dystop_aggregate_panel"


def read(ctx):
    peaks = ctx["peaks"]
    spent = devtrace.kernel_s(ctx["trace"], KERNEL)
    if peaks is None or spent is None:
        return None
    p = work_ssm.ssm_param_count(ctx["config"]["model"])
    least = sum(work.roofline_s(*work.eq4_panel_work(k, u, p), peaks)[0]
                for k, u in ctx["session"].mix_shapes())
    return 100.0 * least / spent
