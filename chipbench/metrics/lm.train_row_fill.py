"""Share of the train rows dispatched that train an activated worker (the
window's activations, ``sum(round_active)``, over ``counts["train_rows"]``);
the rest are bucket padding, trained in full and written back unchanged."""


def read(ctx):
    h = ctx["session"].history
    counts = getattr(h, "counts", None)
    if not counts or not counts.get("train_rows"):
        return None
    return 100.0 * sum(h.round_active) / counts["train_rows"]
