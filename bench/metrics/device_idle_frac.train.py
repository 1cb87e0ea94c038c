"""Share of the traced window in which no operation ran on the device,
training cells (moves train_tokens_per_s)."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * tr.idle_frac if tr is not None and tr.window_s > 0 else None
