"""Host milliseconds a round spends in Eq. 11 evals (``eval_wall_s``),
spread over all the window's rounds."""


def read(ctx):
    if not ctx["units"]:
        return None
    return 1e3 * ctx["spans"]["eval_wall_s"] / ctx["units"]
