"""The server step's Pallas aggregation kernels (kernels/wcwmed.py,
wctma_fused.py, wreduce.py via agg/registry.py) as a share of the
aggregate's memory roofline: one read of the (m, d) float32 momenta and one
write of the (d,) result per step, over the kernels' summed device time and
the chip's HBM bandwidth (moves server_updates_per_s)."""
from bench.counts import agg_one_read_bytes

KERNELS = r"wcwmed|wctma|anchor_dist|wcomb|sqdist|gm_step"


def read(ctx):
    r, tr = ctx["records"], ctx["trace"]
    if tr is None or not r.get("steps_traced"):
        return None
    t = tr.op_seconds(KERNELS)
    if t <= 0:
        return None
    nbytes = r["steps_traced"] * agg_one_read_bytes(r["m"], r["d"])
    return 100.0 * nbytes / t / ctx["peaks"]["hbm_bytes_per_s"]
