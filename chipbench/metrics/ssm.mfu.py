"""Model FLOPs of the trained tokens over the window, as a share of the
chip's bf16 peak, for a Mamba-2 configuration.  FLOPs per token come from
the configuration's shapes (``work_ssm.ssm_train_flops_per_token``);
padding rows of a bucket are not trained tokens."""
import work_ssm


def read(ctx):
    sess, peaks = ctx["session"], ctx["peaks"]
    if peaks is None or not sess.tokens():
        return None
    per_token = work_ssm.ssm_train_flops_per_token(ctx["config"]["model"])
    chips = ctx["cell"]["chips"]
    return 100.0 * per_token * sess.tokens() / ctx["window_s"] / (
        chips * peaks["bf16_flops"])
