"""Shared set-up of the benchmark's own tests: a throw-away checkout that
holds ``BENCHMARK.json`` and ``benchmark/`` with tiny cells added as data
files alone, and a fixture that undoes what a run does to the process."""

from __future__ import annotations

import gc
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = BENCH["workloads"][0]


def make_root(tmp_path: Path, n_ops: int = 600, crash_prob: float = 0.05,
              align_rows: int = 64) -> str:
    """A checkout with one more configuration (``tiny-c5``) and the cell
    ``tiny``: new files and new entries in ``BENCHMARK.json`` only, no
    edit to a file the benchmark has. ``tiny`` sends the committed
    cell's traffic with its sizes cut to ``n_ops``-op histories: the
    same strata and corrupted copies, the chunk boundary the corruption
    is snapped to cut to ``align_rows``."""
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    config = next(c for c in bench["configs"]
                  if c["name"] == CELL["config"])
    cfg = json.loads((REPO / config["file"]).read_text())
    cfg["name"] = "tiny-c5"
    cfg["history"].update(n_ops=n_ops, crash_prob=crash_prob)
    (root / "benchmark/configs/tiny-c5.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": "tiny-c5", "source": "test",
                             "file": "benchmark/configs/tiny-c5.json",
                             "reduced": ["n_ops", "crash_prob"],
                             "why": "test"})
    traffic = json.loads((REPO / "benchmark/workloads" /
                          f"{CELL['traffic']}.json").read_text())
    traffic["corrupt"]["align_rows"] = align_rows
    (root / "benchmark/workloads/tiny-traffic.json").write_text(
        json.dumps(traffic))
    bench["workloads"].append({"name": "tiny", "config": "tiny-c5",
                               "traffic": "tiny-traffic", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL["name"] in m.get("workloads", ()):
            m["workloads"].append("tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.fixture
def isolated(monkeypatch):
    """Undo what :func:`benchmark.harness.measure` does to the process:
    its environment, its layer spans around the program's functions, its
    compile-cache settings and its frozen garbage collector."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from jepsen_tpu.lin import prepare, witness

    for mod, attr in ((prepare, "prepare"), (witness, "tail_replay"),
                      (witness, "tail_replay_sparse")):
        monkeypatch.setattr(mod, attr, getattr(mod, attr))
    for var in ("JEPSEN_TPU_QUARANTINE", "JAX_COMPILATION_CACHE_DIR"):
        monkeypatch.setenv(var, os.environ.get(var, ""))
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    gc.unfreeze()
