"""The robust train step's gradient passes as a share of the chip's peak
bf16 FLOP/s: the FLOPs ``mfu.train`` counts (two forward and backward passes
per token, at x_t and at x_{t-1}; recomputation not counted) over the union
of the program's ``robust_step/grad_x`` and ``robust_step/grad_xprev``
device time, with the ops XLA adds for them (bench/scopes.py), which
leaves the aggregation out (moves train_tokens_per_s). None where the ops
named in a phase cover less than 90 % of busy device time."""
from bench import scopes
from bench.counts import train_flops_per_token


def read(ctx):
    r = ctx["records"]
    if not r.get("steps_traced"):
        return None
    t = scopes.phase_seconds(ctx, scopes.GRAD_X, scopes.GRAD_XPREV)
    if t is None:
        return None
    flops = (r["steps_traced"] * r["tokens_per_step"]
             * train_flops_per_token(ctx["config"], r["seq"]))
    return 100.0 * flops / t / ctx["peaks"]["bf16_flops"]
