"""omega-CWMed median kernel inside the robust train step's aggregation
(kernels/wcwmed.py via dist/robust.py), as a share of its memory roofline:
one read of the G group momenta and one write of the median, in the state's
dtype, per step, over the kernel's device time and the chip's HBM bandwidth
(moves train_tokens_per_s)."""
from bench.counts import param_count

KERNEL = r"wcwmed"   # the kernel's jitted wrapper, as it appears in the trace


def read(ctx):
    r, tr = ctx["records"], ctx["trace"]
    if not r.get("steps_traced") or tr is None:
        return None
    t = tr.op_seconds(KERNEL)
    if t <= 0:
        return None
    d = param_count(ctx["config"])
    nbytes = r["steps_traced"] * (r["groups"] + 1) * d * r["state_bytes"]
    return 100.0 * nbytes / t / ctx["peaks"]["hbm_bytes_per_s"]
