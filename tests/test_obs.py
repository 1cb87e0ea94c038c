"""repro.obs: jit-safe telemetry, tracing and reports.

The contract under test is the ISSUE's acceptance bar:

- instrumentation is BEHAVIOR-NEUTRAL — greedy token streams (replicated
  serve under attack) and fleet/engine loss trajectories are identical with
  obs on and off;
- everything a run writes validates against the typed registry (metrics
  JSONL) and the Chrome-trace invariants (trace JSON);
- quarantine transitions are structured events carrying the step, the
  replica's score at eviction, and the in-flight request uids;
- the obs README catalog can never drift from the registry (RD203).
"""
import copy
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AsyncByzantineEngine, AttackConfig, EngineConfig
from repro.core.attacks import LogitAttackConfig
from repro.fleet import Scenario, run_scenarios
from repro.models import ModelConfig, init_lm
from repro.obs import (EVENTS, MASS_EDGES, REGISTRY, MetricSink, RunObs,
                       Tracer, histogram, load_jsonl, register,
                       register_event, render_summary, validate_jsonl,
                       validate_trace)
from repro.obs.metrics import TIME_EDGES, bucketize
from repro.optim import OptConfig
from repro.serve import (ReplicatedConfig, ReplicatedServeEngine, ServeConfig,
                         ServeEngine, synth_workload)

V = 64
DENSE = ModelConfig(name="dense", n_layers=2, d_model=32, n_heads=4, n_kv=2,
                    d_ff=64, vocab=V, qkv_bias=True)
SCFG = ServeConfig(n_slots=4, max_len=32, max_prefill_batch=2)


@pytest.fixture(scope="module")
def dense_params():
    return init_lm(jax.random.PRNGKey(0), DENSE)


def _workload(n=6, seed=0):
    return synth_workload(n, V, seed=seed, prompt_lens=(4, 12),
                          gen_lens=(2, 6), rate=0.0)


# ---------------------------------------------------------------------------
# histogram: device collection and its host twin agree
# ---------------------------------------------------------------------------

def test_histogram_matches_host_bucketize():
    vals = np.array([0.0, 0.005, 0.01, 0.03, 0.15, 0.5, 0.95, 2.0])
    dev = np.asarray(histogram(jnp.asarray(vals), MASS_EDGES))
    host = bucketize(vals.tolist(), MASS_EDGES)
    assert dev.shape == (len(MASS_EDGES) + 1,)
    assert float(dev.sum()) == len(vals)
    np.testing.assert_allclose(dev, host)


def test_histogram_edge_is_right_open():
    # a value exactly on an edge lands in the bucket ABOVE it (half-open
    # [lo, hi) buckets) — both on device and on host
    dev = np.asarray(histogram(jnp.asarray([0.1]), MASS_EDGES))
    host = bucketize([0.1], MASS_EDGES)
    idx = list(MASS_EDGES).index(0.1) + 1
    assert dev[idx] == 1.0 and host[idx] == 1.0


def test_histogram_weights_accumulate_mass():
    vals = jnp.asarray([0.05, 0.06, 0.5])
    w = jnp.asarray([1.0, 2.0, 4.0])
    out = np.asarray(histogram(vals, MASS_EDGES, weights=w))
    assert float(out.sum()) == 7.0


def test_histogram_is_jittable():
    f = jax.jit(lambda v: histogram(v, TIME_EDGES))
    out = np.asarray(f(jnp.asarray([1e-5, 2e-3, 0.5])))
    assert out.shape == (len(TIME_EDGES) + 1,) and out.sum() == 3


# ---------------------------------------------------------------------------
# registry + sink: typed, conflict-checked, schema-validated
# ---------------------------------------------------------------------------

def test_register_conflict_raises():
    register("test.obs.gauge", "gauge", unit="x", desc="test")  # idempotent
    register("test.obs.gauge", "gauge", unit="x", desc="test")
    with pytest.raises(ValueError, match="different spec"):
        register("test.obs.gauge", "counter", unit="x", desc="test")
    with pytest.raises(ValueError, match="unknown kind"):
        register("test.obs.bad", "timer")
    with pytest.raises(ValueError, match="bucket_edges"):
        register("test.obs.hist", "histogram")
    register_event("test.obs.event", desc="e")
    with pytest.raises(ValueError, match="different description"):
        register_event("test.obs.event", desc="changed")


def test_sink_rejects_unregistered_names(tmp_path):
    sink = MetricSink(tmp_path / "m.jsonl")
    with pytest.raises(KeyError, match="not registered"):
        sink.log("no.such.metric", 1.0)
    with pytest.raises(KeyError, match="not registered"):
        sink.event("no.such.event")
    sink.close()


def test_sink_jsonl_roundtrip_validates(tmp_path):
    path = tmp_path / "m.jsonl"
    sink = MetricSink(path)
    sink.log("engine.loss", jnp.asarray(1.5), step=1, worker=3)
    sink.log("engine.weight_mass", jnp.asarray([0.25, 0.75]), step=1)
    sink.log("engine.weight_mass_hist",
             histogram(jnp.asarray([0.25, 0.75]), MASS_EDGES), step=1)
    sink.event("serve.quarantine.evict", step=2, replica=1, score=-0.5,
               backoff=3, requests=[0, 1])
    sink.close()
    assert validate_jsonl(path) == []
    rows = load_jsonl(path)
    assert len(rows) == 4
    assert rows[0] == {"metric": "engine.loss", "kind": "gauge",
                       "unit": "nats", "step": 1, "value": 1.5, "worker": 3}
    assert rows[3]["event"] == "serve.quarantine.evict"
    assert rows[3]["requests"] == [0, 1]


def test_validation_catches_schema_breaks(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text(
        '{"metric": "engine.loss", "kind": "gauge", "unit": "nats", '
        '"step": 1, "value": "oops"}\n'
        '{"metric": "nope", "value": 1.0}\n'
        '{"event": "nope.event"}\n'
        '{"metric": "engine.weight_mass_hist", "kind": "histogram", '
        '"unit": "workers", "step": 1, "value": [1, 2]}\n')
    errors = validate_jsonl(path)
    assert len(errors) == 4
    assert any("non-numeric" in e for e in errors)
    assert any("unregistered metric" in e for e in errors)
    assert any("unregistered event" in e for e in errors)
    assert any("buckets" in e for e in errors)


# ---------------------------------------------------------------------------
# tracer: Chrome-trace invariants
# ---------------------------------------------------------------------------

def test_tracer_exports_valid_chrome_trace(tmp_path):
    path = tmp_path / "t.trace.json"
    tr = Tracer(path)
    with tr.span("prefill", n=2):
        pass
    tr.instant("serve.request.admit", uid=0, slot=1)
    tr.counter("serve.queue", depth=3)
    tr.begin_async("request", 0, prompt_len=4)
    tr.end_async("request", 0, gen_tokens=2)
    tr.close()
    assert path.exists()
    assert validate_trace(path) == []
    import json
    doc = json.loads(path.read_text())
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"X", "i", "C", "b", "e", "M"} <= phases


def test_tracer_span_lands_on_the_profile_host_plane(tmp_path):
    """A span taken while the profiler runs is on the profile's host plane,
    on the clock of the device's ops, and still in the tracer's own export."""
    from jax.profiler import ProfileData
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("obs.test_span"):
            jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(sorted(tmp_path.rglob("*.xplane.pb"))[-1]))
    host = {ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert "obs.test_span" in host
    assert [e["name"] for e in tr.export()["traceEvents"]
            if e["ph"] == "X"] == ["obs.test_span"]


# ---------------------------------------------------------------------------
# RunObs: the one handle the engines take
# ---------------------------------------------------------------------------

def test_runobs_tolerates_missing_halves():
    obs = RunObs()              # no sink, no tracer: everything no-ops
    obs.metric("engine.loss", 1.0)
    obs.event("serve.request.admit", uid=0)
    with obs.span("decode"):
        pass
    obs.counter("serve.queue", depth=1)
    obs.request_begin(0)
    obs.request_end(0)
    obs.close()


# ---------------------------------------------------------------------------
# core engine: obs on == obs off, staleness host-derived
# ---------------------------------------------------------------------------

def _engine_cfg():
    return EngineConfig(m=5, byz=(4,), arrival="proportional",
                        attack=AttackConfig("sign_flip"), agg="ctma:cwmed",
                        lam=0.3,
                        opt=OptConfig(name="mu2", lr=0.02, gamma=0.1,
                                      beta=0.25))


def _loss_fn(w, batch):
    return 0.5 * jnp.mean(jnp.sum((w - batch["x"]) ** 2, -1)) \
        + 0.0 * jnp.sum(batch["y"])


def _drive_engine(collect, obs=None, steps=8, seed=0):
    cfg = _engine_cfg()
    eng = AsyncByzantineEngine(cfg, _loss_fn, 12, collect_metrics=collect)
    rng = np.random.default_rng(seed)
    st = eng.init(jnp.zeros((12,)),
                  {"x": jnp.asarray(rng.normal(size=(cfg.m, 4, 12)),
                                    jnp.float32),
                   "y": jnp.zeros((cfg.m, 4), jnp.int32)})

    def batches():
        while True:
            yield {"x": jnp.asarray(rng.normal(size=(4, 12)), jnp.float32),
                   "y": jnp.zeros((4,), jnp.int32)}

    st, _ = eng.run(st, batches(), steps, obs=obs)
    return np.asarray(st.x)


def test_engine_obs_trajectory_parity(tmp_path):
    ref = _drive_engine(collect=False)
    obs = RunObs(sink=MetricSink(tmp_path / "e.jsonl"))
    instrumented = _drive_engine(collect=True, obs=obs)
    obs.close()
    np.testing.assert_array_equal(ref, instrumented)
    assert validate_jsonl(tmp_path / "e.jsonl") == []
    names = {r.get("metric") for r in load_jsonl(tmp_path / "e.jsonl")}
    assert {"engine.loss", "engine.lambda_emp", "engine.staleness",
            "engine.weight_mass", "engine.weight_mass_hist",
            "engine.byz_mass", "engine.anchor_dist"} <= names


def test_engine_staleness_is_gap_since_previous_arrival(tmp_path):
    obs = RunObs(sink=MetricSink(tmp_path / "s.jsonl"))
    _drive_engine(collect=False, obs=obs, steps=20)
    obs.close()
    rows = [r for r in load_jsonl(tmp_path / "s.jsonl")
            if r.get("metric") == "engine.staleness"]
    assert len(rows) == 20
    last = {}
    for r in rows:
        expect = r["step"] - last.get(r["worker"], r["step"])
        assert r["value"] == expect, r
        last[r["worker"]] = r["step"]
    # the arrival process must actually produce a nonzero staleness
    assert any(r["value"] > 0 for r in rows)


# ---------------------------------------------------------------------------
# fleet: loss trajectory parity, per-scenario rows
# ---------------------------------------------------------------------------

FLEET = [Scenario(problem="quadratic", attack="sign_flip", agg="ctma:cwmed",
                  m=5, byz_frac=0.2, steps=6, batch=4, seed=0, name="a"),
         Scenario(problem="quadratic", attack="sign_flip", agg="ctma:cwmed",
                  m=5, byz_frac=0.2, steps=6, batch=4, seed=3, name="b")]


def test_fleet_obs_trajectory_parity(tmp_path):
    ref = run_scenarios([sc for sc in FLEET])
    obs = RunObs.open(tmp_path, "fleet", compile_events=False)
    instrumented = run_scenarios([sc for sc in FLEET], obs=obs)
    obs.close()
    for a, b in zip(ref, instrumented):
        assert a.eval["loss"] == b.eval["loss"]
        np.testing.assert_array_equal(np.asarray(a.state.x),
                                      np.asarray(b.state.x))
    assert validate_jsonl(tmp_path / "fleet.metrics.jsonl") == []
    rows = load_jsonl(tmp_path / "fleet.metrics.jsonl")
    losses = [r for r in rows if r.get("metric") == "fleet.loss"]
    assert len(losses) == 6                      # one vector row per step
    assert all(len(r["value"]) == 2 for r in losses)   # (S,) per group
    groups = [r for r in rows if r.get("event") == "fleet.group"]
    assert len(groups) == 1 and len(groups[0]["scenarios"]) == 2
    names = {r.get("metric") for r in rows}
    assert {"engine.weight_mass", "engine.byz_mass",
            "engine.anchor_dist"} <= names       # device metrics were on


# ---------------------------------------------------------------------------
# serve: token-stream parity under attack + structured quarantine events
# ---------------------------------------------------------------------------

RCFG = ReplicatedConfig(n_replicas=3, byz=(2,),
                        attack=LogitAttackConfig(name="sign_flip"),
                        quarantine_after=2, readmit_after=3)


def _run_replicated(cfg, params, obs=None):
    eng = ReplicatedServeEngine(cfg, params, SCFG, RCFG, obs=obs)
    return eng.run([copy.deepcopy(r) for r in _workload()])


def test_replicated_obs_token_parity_and_artifacts(tmp_path, dense_params):
    ref = _run_replicated(DENSE, dense_params)
    obs = RunObs.open(tmp_path, "serve")
    rep = _run_replicated(DENSE, dense_params, obs=obs)
    obs.close()

    # byte-identical greedy streams with telemetry on
    assert rep.outputs == ref.outputs

    mpath = tmp_path / "serve.metrics.jsonl"
    tpath = tmp_path / "serve.trace.json"
    assert validate_jsonl(mpath) == []
    assert validate_trace(tpath) == []

    rows = load_jsonl(mpath)
    names = {r.get("metric") for r in rows}
    assert {"serve.queue_depth", "serve.slot_occupancy", "serve.prefill_s",
            "serve.decode_s", "serve.prefill_s_hist", "serve.decode_s_hist",
            "serve.prefill_tokens", "serve.gen_tokens",
            "serve.replica.vote_mass", "serve.replica.score",
            "serve.vote.disagree_mass", "serve.vote.margin"} <= names
    events = {r.get("event") for r in rows}
    assert {"serve.request.admit", "serve.request.finish",
            "serve.quarantine.evict"} <= events

    # vote-mass rows are (R,) vectors; the byz replica's mass hits zero
    masses = [r["value"] for r in rows
              if r.get("metric") == "serve.replica.vote_mass"]
    assert all(len(v) == RCFG.n_replicas for v in masses)
    # the byz replica's eviction zeroes its vote mass in telemetry (the
    # evict/readmit cycle phase at the final tick depends on tick count,
    # so pin the zero anywhere in the stream, not at the end)
    assert any(v[2] == 0.0 for v in masses)

    # the trace is Perfetto-loadable: named tracks + spans + request pairs
    import json
    doc = json.loads(tpath.read_text())
    names_md = {e["args"]["name"] for e in doc["traceEvents"]
                if e["ph"] == "M"}
    assert {"engine", "requests"} <= names_md
    spans = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"decode", "warmup"} <= spans
    # chunked default: there is no separate prefill phase — prefill chunks
    # ride the unified decode span, marked by the chunk_tokens attr
    assert any(e["name"] == "decode" and e["args"].get("chunk_tokens")
               for e in doc["traceEvents"] if e["ph"] == "X")


def test_quarantine_events_carry_step_score_and_requests(tmp_path,
                                                         dense_params):
    obs = RunObs.open(tmp_path, "q", compile_events=False)
    rep = _run_replicated(DENSE, dense_params, obs=obs)
    obs.close()
    assert rep.quarantine_events, "attack scenario must trigger eviction"
    for ev in rep.quarantine_events:          # report-side enrichment
        assert set(ev) >= {"replica", "step", "backoff", "score", "requests"}
        assert ev["replica"] == 2
        assert isinstance(ev["requests"], list)
    evicts = [r for r in load_jsonl(tmp_path / "q.metrics.jsonl")
              if r.get("event") == "serve.quarantine.evict"]
    assert len(evicts) == len(rep.quarantine_events)
    for row, ev in zip(evicts, rep.quarantine_events):
        assert row["step"] == ev["step"] and row["score"] == ev["score"]
        assert row["requests"] == ev["requests"]


def test_single_engine_obs_parity(tmp_path, dense_params):
    ref = ServeEngine(DENSE, dense_params, SCFG).run(
        [copy.deepcopy(r) for r in _workload()])
    obs = RunObs.open(tmp_path, "single", compile_events=False)
    rep = ServeEngine(DENSE, dense_params, SCFG, obs=obs).run(
        [copy.deepcopy(r) for r in _workload()])
    obs.close()
    assert rep.outputs == ref.outputs
    assert validate_jsonl(tmp_path / "single.metrics.jsonl") == []


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------

def test_report_renders_run_sections(tmp_path, dense_params):
    obs = RunObs.open(tmp_path, "r", compile_events=False)
    _run_replicated(DENSE, dense_params, obs=obs)
    obs.close()
    rows = load_jsonl(tmp_path / "r.metrics.jsonl")
    import json
    doc = json.loads((tmp_path / "r.trace.json").read_text())
    for fmt in ("text", "md"):
        out = render_summary(rows, trace_doc=doc, fmt=fmt)
        assert "serve.decode_s" in out
        assert "Quarantine timeline" in out
        assert "Per-replica health" in out
    text = render_summary(rows, trace_doc=doc, fmt="text")
    assert "replica 2" in text


def test_obs_cli_validate_and_summarize(tmp_path, dense_params, capsys):
    from repro.launch.obs import main
    obs = RunObs.open(tmp_path, "cli", compile_events=False)
    _run_replicated(DENSE, dense_params, obs=obs)
    obs.close()
    m, t = str(tmp_path / "cli.metrics.jsonl"), str(tmp_path / "cli.trace.json")
    assert main(["--validate", "--metrics", m, "--trace", t]) == 0
    assert "OK" in capsys.readouterr().out
    assert main(["--metrics", m, "--trace", t, "--format", "md"]) == 0
    assert "Per-replica health" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# docs: the registry <-> README catalog contract (RD203)
# ---------------------------------------------------------------------------

def test_every_registered_name_in_obs_readme():
    from pathlib import Path
    readme = (Path(__file__).resolve().parents[1] / "src" / "repro" / "obs"
              / "README.md").read_text()
    missing = [n for n in list(REGISTRY) + list(EVENTS)
               if not n.startswith("test.") and n not in readme]
    assert missing == [], f"obs README catalog is missing {missing}"


def test_rd203_fires_on_undocumented_metric(tmp_path):
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    from lint.docs_rules import check_metric_catalog
    obs_dir = tmp_path / "src" / "repro" / "obs"
    obs_dir.mkdir(parents=True)
    (obs_dir / "metrics.py").write_text(
        'register("x.documented", "gauge")\n'
        'register("x.undocumented", "gauge")\n'
        'register_event("x.event")\n')
    (obs_dir / "README.md").write_text("`x.documented` and `x.event`\n")
    findings = check_metric_catalog(tmp_path)
    assert [f.code for f in findings] == ["RD203"]
    assert "x.undocumented" in findings[0].message
    # documenting it clears the finding
    (obs_dir / "README.md").write_text(
        "`x.documented` `x.undocumented` `x.event`\n")
    assert check_metric_catalog(tmp_path) == []
