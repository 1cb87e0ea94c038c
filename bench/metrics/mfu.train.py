"""Whole robust training step's share of the chip's peak bf16 FLOP/s: the
FLOPs the algorithm needs for the tokens of the steps traced (two forward
and backward passes per token, at x_t and at x_{t-1}; recomputation not
counted) over the traced window (moves train_tokens_per_s)."""
from bench.counts import train_flops_per_token


def read(ctx):
    r, tr = ctx["records"], ctx["trace"]
    if not r.get("steps_traced") or tr is None:
        return None
    flops = (r["steps_traced"] * r["tokens_per_step"]
             * train_flops_per_token(ctx["config"], r["seq"]))
    return 100.0 * flops / tr.window_s / ctx["peaks"]["bf16_flops"]
