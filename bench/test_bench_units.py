"""CPU tests of the benchmark's yardstick: the table of peaks, the operation
and byte counts, the trace reduction, and the manifest's consistency."""
from __future__ import annotations

import json
import re
from pathlib import Path

import numpy as np
import pytest

from bench import counts, harness, trace_reduce

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
QWEN = json.loads((HERE / "configs" / "qwen2-1.5b.json").read_text())


# ---------------------------------------------------------------------------
# peaks
# ---------------------------------------------------------------------------

def test_peaks_known_device():
    p = counts.load_peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_peaks_unknown_device_raises():
    with pytest.raises(KeyError, match="no peaks"):
        counts.load_peaks("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def test_qwen2_param_count_tied():
    # 151936 x 1536 tied embedding + 28 layers of 46.8 M + final norm
    assert counts.param_count(QWEN) == 1_543_714_304
    assert counts.layer_params(QWEN) == 46_797_824


def test_untied_head_counts_twice():
    untied = dict(QWEN, tie_word_embeddings=False)
    assert (counts.param_count(untied) - counts.param_count(QWEN)
            == QWEN["vocab_size"] * QWEN["hidden_size"])


def test_mu2_flops_per_token():
    c = dict(QWEN, num_hidden_layers=4)
    mm = 4 * (1536 * 1536 * 2 + 2 * 1536 * 256 + 3 * 1536 * 8960) + 1536 * 151936
    attn = 4 * 4 * 12 * 128 * (512 + 1) / 2
    assert counts.matmul_params(c) == mm
    assert counts.train_flops_per_token(c, 512) == pytest.approx(
        2 * 3 * (2 * mm + attn))


def test_kv_bytes_per_token():
    assert counts.kv_bytes_per_token(QWEN) == 28 * 2 * 2 * 128 * 2 == 28672


def test_ragged_attn_bytes():
    q = 28 * 12 * 128 * 2
    assert counts.ragged_attn_bytes(QWEN, [(1, 100), (16, 32)]) == (
        132 * 28672 + 2 * 17 * q)


def test_agg_one_read_bound():
    assert counts.agg_one_read_bytes(17, 1 << 25) == 18 * (1 << 25) * 4


# ---------------------------------------------------------------------------
# the reference's weighted median and the change comparison
# ---------------------------------------------------------------------------

def _median_by_definition(col, s):
    """Sorted ascending (stable) with weights carried along, the first value
    whose cumulative weight passes half; a prefix at exactly half takes the
    mean of its last value and the next."""
    order = np.argsort(col, kind="stable")
    xs, cw = col[order], np.cumsum(s[order])
    half = 0.5 * cw[-1]
    for j in range(len(xs) - 1):
        if cw[j] == half:
            return 0.5 * (xs[j] + xs[j + 1])
    return xs[np.argmax(cw > half)]


@pytest.mark.parametrize("m", [1, 2, 4, 5, 17])
def test_weighted_median_matches_its_definition(m):
    import jax.numpy as jnp
    from bench.reference.robust import weighted_median
    rng = np.random.default_rng(m)
    x = rng.integers(-3, 4, (m, 400)).astype(np.float32)    # many ties
    for s in (np.full(m, 2.0), rng.integers(0, 4, m) + (np.arange(m) == 0)):
        s = s.astype(np.float32)
        want = [_median_by_definition(x[:, j], s) for j in range(x.shape[1])]
        got = np.asarray(weighted_median(jnp.asarray(x), jnp.asarray(s)))
        np.testing.assert_array_equal(got, np.float32(want))


def test_change_gap_median_and_worst_leaf():
    from bench.drivers.train import change_gap
    want = {"w": {"a": 1.0, "b": 2.0, "c": 4.0}, "x": {"a": 1.0, "b": 1.0, "c": 1.0}}
    got = {"w": {"a": 1.0, "b": 2.2, "c": 4.0}, "x": {"a": 1.0, "b": 1.0, "c": 0.0}}
    names = ["a", "b", "c"]
    # w gaps 0, 0.1, 0; x gaps 0, 0, 1
    assert change_gap(got, want, names) == pytest.approx(1.0)
    assert change_gap(got, want, names, median=True) == pytest.approx(0.0)
    frozen = {k: {n: 0.0 for n in names} for k in want}
    assert change_gap(frozen, want, names) == pytest.approx(1.0)


def test_zipf_tokens_follow_their_law():
    from bench.drivers.train import make_tokens
    from bench.model import seed_words
    c, t = {"vocab_size": 4096}, {"n_batches": 2, "rows": 16, "seq": 511,
                                  "zipf": 1.0}
    words = seed_words(2 ** 33 + 5)
    tok = np.asarray(make_tokens(c, t, words))
    assert tok.shape == (2, 16, 512) and tok.min() >= 0 and tok.max() < 4096
    np.testing.assert_array_equal(tok, np.asarray(make_tokens(c, t, words)))
    counts = np.sort(np.bincount(tok.ravel(), minlength=4096))[::-1]
    harmonic = np.sum(1.0 / np.arange(1, 4097))
    share = counts[:3] / tok.size             # ranks 1, 2, 3 in Zipf(1)
    np.testing.assert_allclose(share, 1.0 / (np.arange(1, 4) * harmonic),
                               rtol=0.1)


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def _synthetic():
    """Two devices; host spans bench.window [0, 10 us] and bench.host_wait
    [4, 9 us]. Device 0 runs ops [0, 2] and [1, 3] (overlapping) and [9.5, 10];
    device 1 runs [0, 5]."""
    from jax.profiler import ProfileData

    def ev(mid, start_us, dur_us, stats=""):
        return (f"events {{ metadata_id: {mid} offset_ps: {int(start_us * 1e6)} "
                f"duration_ps: {int(dur_us * 1e6)} {stats} }}")

    tf = 'stats { metadata_id: 7 str_value: "jit(step)/jit(wcwmed_pallas)/pallas_call" }'
    # a fusion that reads the kernel's output names it in its text only
    txt = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {ev(1, 0, 2)} {ev(2, 1, 2, tf)} {ev(1, 9.5, 0.5)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {ev(3, 0, 3)} {ev(3, 9.5, 0.5)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[8]{{0}} fusion(f32[8]{{0}} %wcwmed_pallas.3)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%wcwmed_pallas.3 = f32[8]{{0}} custom-call(bf16[4,8]{{1,0}} %p)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit_step(123)" }} }}
  stat_metadata {{ key: 7 value {{ id: 7 name: "tf_op" }} }} }}
planes {{ id: 2 name: "/device:TPU:1"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ev(1, 0, 5)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }} }}
planes {{ id: 3 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {ev(1, 0, 10)} {ev(2, 4, 5)} {ev(3, 0, 1)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.host_wait" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "other.span" }} }} }}
"""
    return ProfileData.from_text_proto(txt)


def test_busy_is_union_of_intervals_averaged_over_devices():
    s = trace_reduce.reduce_trace(_synthetic())
    assert s.n_devices == 2
    assert s.window_s == pytest.approx(10e-6)
    # device 0: [0, 3] + [9.5, 10] = 3.5 us; device 1: 5 us
    assert s.busy_s == pytest.approx((3.5e-6 + 5e-6) / 2)
    assert s.idle_frac == pytest.approx(1 - 4.25 / 10)


def test_per_op_device_time_and_labels():
    s = trace_reduce.reduce_trace(_synthetic())
    assert s.ops["fusion.1"].seconds == pytest.approx(2e-6 + 0.5e-6 + 5e-6)
    assert s.ops["fusion.1"].count == 3
    assert s.op_seconds("wcwmed") == pytest.approx(2e-6 / 2)
    assert s.module_seconds(r"^jit_step") == pytest.approx((3.5e-6 / 2, 1))


def test_idle_gaps_attributed_to_harness_spans():
    s = trace_reduce.reduce_trace(_synthetic())
    # device 0: gap [3, 9.5] (midpoint in host_wait); device 1: [5, 10]
    # (midpoint 7.5, host_wait); spans without the bench. prefix are ignored
    assert s.idle_gaps == pytest.approx({"bench.host_wait": (6.5e-6 + 5e-6) / 2})
    b = s.breakdown()
    assert b["device_ops"][0][0] == "fusion.1"
    assert b["idle_gaps"] == [["bench.host_wait", pytest.approx(5.75e-6)]]


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5 lite: three matmul calls with a host
    sleep between them, inside bench.window."""
    s = trace_reduce.reduce_trace(trace_reduce.load(HERE / "testdata"))
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.idle_gaps.get("bench.host_wait", 0.0) > 0.003
    assert s.op_count(r"fusion|convolution|dot") >= 3


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_manifest_names_and_units():
    names = ([c["name"] for c in MAN["configs"]]
             + [w["name"] for w in MAN["workloads"]]
             + [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in MAN["workloads"]]:
        assert NAME.match(n), n
    for c in MAN["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k), k
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_cell_files_resolve(cell):
    c = harness.load_cell(cell, ROOT, MAN)
    assert (HERE / "drivers" / f"{c.traffic['driver']}.py").is_file()
    assert c.config["name"] in c.traffic["limits"]
    for m in c.per_layer:
        assert callable(harness.load_reader(m["name"]))
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer, cell
    for m in c.per_layer:
        assert m["moves"] in reported, (m["name"], cell)


def test_config_files_state_what_they_cut():
    for conf in MAN["configs"]:
        c = json.loads((ROOT / conf["file"]).read_text())
        assert c["name"] == conf["name"] and c["source"] == conf["source"]
        assert sorted(c["reduced"]) == sorted(conf["reduced"])


def test_at_most_one_four_chip_cell():
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= 1
    assert all(w["chips"] in (1, 4) for w in MAN["workloads"])
