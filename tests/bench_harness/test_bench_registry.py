"""The benchmark finds its configurations, cells and metric readers by
name, picks up a cell added as data files alone, prints the contract's
last line, and refuses to run without a TPU."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_helpers import REPO, isolated, make_root  # noqa: F401

from benchmark import harness

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "checks"}


def test_benchmark_json_has_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert isinstance(w["chips"], int) and w["chips"] > 0
        assert len(w["why"]) <= 200
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["bound"] > 0
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
@pytest.mark.parametrize("trace", [False, True])
def test_cells_configs_and_readers_are_found_by_name(cell, trace):
    c = harness.find_cell(str(REPO), cell, trace)
    assert c.metrics, f"{cell} reports no metric with trace={trace}"
    if not trace:
        assert {m["name"] for m in c.metrics} >= {"setup_s", "ops_per_s"}
    for m in c.metrics:
        assert callable(harness.reader(str(REPO), m["name"]).read)
    gen = harness.generator(str(REPO), c.config)
    ref = harness.reference(str(REPO), c.config)
    assert callable(gen.generate) and callable(ref.expected)
    assert c.traffic.get("strata")


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(KeyError):
        harness.find_cell(str(REPO), "no-such-cell", False)
    with pytest.raises(FileNotFoundError):
        harness.reader(str(REPO), "no_such_metric")


@pytest.mark.parametrize("trace", [False, True])
def test_cell_added_as_data_runs_and_prints_the_contract_line(
        tmp_path, isolated, trace):  # noqa: F811
    root = make_root(tmp_path)
    out = harness.measure(root, "tiny", 2**40 + 3, 0.5, trace,
                          t_start=0.0, require_tpu=False)
    assert list(out)[-1] == "checks"
    assert set(out) == RESULT_KEYS | ({"breakdown"} if trace else set())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    want = {"ops_per_s", "setup_s"} if not trace else \
        {"pack_share", "compiles_in_window"}
    assert want <= set(out["metrics"])
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(out["device"])


class _Device:
    platform, device_kind = "tpu", "TPU v5 lite"


@pytest.mark.parametrize("chips,seen", [(1, 1), (4, 4), (4, 1)])
def test_device_info_holds_the_cell_to_its_chips(monkeypatch, chips, seen):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_Device()] * seen)
    if seen < chips:
        with pytest.raises(harness.NoAccelerator, match=f"{chips} chips"):
            harness.device_info(chips)
    else:
        assert harness.device_info(chips)["count"] == chips


def _run_cli(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_without_a_tpu_exits_nonzero_with_no_result():
    p = _run_cli(REPO, BENCH["workloads"][0]["name"])
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
    assert "no TPU" in p.stderr


def test_command_in_a_bare_checkout_exits_nonzero_with_no_result(tmp_path):
    bare = tmp_path / "bare"
    shutil.copytree(REPO / "benchmark", bare / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", bare / "BENCHMARK.json")
    p = _run_cli(bare, BENCH["workloads"][0]["name"])
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
