"""The robust train step's whole aggregation layer as a share of its memory
roofline: the minimum traffic ``cwmed_kernel_roofline.train`` counts (one
read of the G group momenta and one write of the median, in the state's
dtype, per step) over the device time of the program's
``robust_step/aggregate`` phase (anchor, distance pass and trimmed combine,
with the relayouts XLA adds for them; bench/scopes.py) and the chip's HBM
bandwidth (moves train_tokens_per_s). None where the ops named in a phase
cover less than 90 % of busy device time."""
from bench import scopes
from bench.counts import param_count


def read(ctx):
    r = ctx["records"]
    if not r.get("steps_traced"):
        return None
    t = scopes.phase_seconds(ctx, scopes.AGGREGATE)
    if t is None:
        return None
    d = param_count(ctx["config"])
    nbytes = r["steps_traced"] * (r["groups"] + 1) * d * r["state_bytes"]
    return 100.0 * nbytes / t / ctx["peaks"]["hbm_bytes_per_s"]
