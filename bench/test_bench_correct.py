"""The comparison that decides ``correct``, shown to fail: each fault a
cell can have, planted under the timed path, must come out not correct with
the cell's own limits, and the sound program must come out correct; each
cell's control (the plain reference at the precision step below the
configuration's, in the program's place) must stand out from the sound
program. The whole run is driven except the look for a chip, on the CPU at
sizes a test run holds.
"""
from __future__ import annotations

import copy
import json
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parent.parent
SEED = 2 ** 33 + 12345          # wider than 32 bits, as seeds may be

# widths and lengths a CPU test run holds; every other setting is the cell's
SMALL_MODEL = dict(hidden_size=128, num_attention_heads=4,
                   num_key_value_heads=2, intermediate_size=256,
                   vocab_size=4096, num_hidden_layers=2)
SMALL = {
    "train.qwen2-1.5b.ctma-cwmed": ("qwen2-1.5b-4l", "train-ctma-cwmed",
                                    SMALL_MODEL, dict(rows=8, seq=64, n_batches=4)),
    "server.qwen2-1.5b.m17": ("qwen2-1.5b", "server-m17", {},
                              dict(d=1 << 14, n_batches=8)),
}


def small_cell(name: str) -> harness.Cell:
    """The cell at a test's size: from the manifest where it is a workload,
    else from its configuration and traffic files alone."""
    config, mix, model, traffic = SMALL[name]
    if name in {w["name"] for w in harness.load_manifest(ROOT)["workloads"]}:
        cell = copy.deepcopy(harness.load_cell(name, ROOT))
    else:
        cell = harness.Cell(
            name=name,
            config=json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text()),
            traffic=json.loads((ROOT / "bench" / "traffic" / f"{mix}.json").read_text()),
            chips=1, end_to_end=[], per_layer=[])
    cell.config.update(model)
    cell.traffic.update(traffic)
    return cell


def failed(checks) -> bool:
    return not all(c.ok for c in checks)


@pytest.fixture(scope="module")
def train_runs():
    from bench.drivers import train
    cell = small_cell("train.qwen2-1.5b.ctma-cwmed")
    want = train.reference(cell, SEED)
    return cell, want


@pytest.mark.parametrize("fault", [None, "state_unchanged", "params_unchanged",
                                   "half_batch"])
def test_train_program_and_faults(train_runs, fault):
    from bench.drivers import train
    cell, want = train_runs
    got, rec, _ = train.drive(cell, SEED, 0.5, False, None, fault=fault)
    checks = train.compare(got, want, cell)
    assert rec["compiles_in_window"] == 0
    assert failed(checks) == (fault is not None), [(c.name, c.value) for c in checks]


def test_train_control_fails(train_runs):
    """The control reads at least three times the sound program's reading
    on one of the numbers, the separation a limit needs. Whether it fails
    the cell's own limits is read at the cell's own size on the chip
    (PERF.md): at this size both sides' gaps are smaller."""
    from bench.drivers import train
    cell, want = train_runs
    got, _, _ = train.drive(cell, SEED, 0.5, False, None)
    sound = {c.name: c.value for c in train.compare(got, want, cell)}
    ctl = train.reference(cell, SEED, precision="fp8")
    n = {c.name: c.value for c in train.compare(ctl, want, cell)}
    assert any(n[k] >= 3 * sound[k] for k in n), (n, sound)


# The server driver is built and tested here, but its cell is not in
# BENCHMARK.json yet (PERF.md, Open questions): there are
# no limits from the chip, so these tests compare the sound program with the
# control and the faults instead.

@pytest.fixture(scope="module")
def server_runs():
    from bench.drivers import server
    cell = small_cell("server.qwen2-1.5b.m17")
    want = server.reference(cell, SEED)
    got, _, _ = server.drive(cell, SEED, 0.3, False, None)
    sound = {c.name: c.value for c in server.compare(got, want, cell, SEED)}
    return cell, want, sound


def _server_numbers(checks) -> dict:
    return {c.name: c.value for c in checks}


def test_server_program_matches_reference(server_runs):
    _, _, sound = server_runs
    assert sound["arrivals_differing"] == 0
    assert sound["param_change_rel_err"] < 1e-5 and sound["momenta_rel_err"] < 1e-6


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_server_faults_stand_out(server_runs, fault):
    from bench.drivers import server
    cell, want, sound = server_runs
    got, _, _ = server.drive(cell, SEED, 0.3, False, None, fault=fault)
    n = _server_numbers(server.compare(got, want, cell, SEED))
    assert (n["arrivals_differing"] > 0
            or n["param_change_rel_err"] > 100 * sound["param_change_rel_err"]), n


def test_server_control_stands_out(server_runs):
    from bench.drivers import server
    cell, want, sound = server_runs
    ctl = server.reference(cell, SEED, precision="bf16")
    n = _server_numbers(server.compare(ctl, want, cell, SEED))
    assert n["param_change_rel_err"] > 100 * sound["param_change_rel_err"], n
    assert n["momenta_rel_err"] > 100 * sound["momenta_rel_err"], n
