"""The benchmark harness: one cell, one seed, one run.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name that ``BENCHMARK.json`` gives it:

- ``BENCHMARK.json``'s ``configs[].file``: the deployment (history sizes,
  crash model, the checker's model, its generator and its reference);
- ``benchmark/workloads/<traffic>.json``: the traffic mix (the pool's
  strata and its corrupted copies);
- ``benchmark/traffic/<generator>.py`` and
  ``benchmark/reference/<reference>.py``: named by the configuration;
- ``benchmark/metrics/<metric>.py``: one reader per metric, with
  ``read(run) -> float | None``.

The run: set-up builds the cell's pool of histories from ``--seed`` and
decides each one once (compile or cache load, warm-up); the window then
hands the pool's histories, in a closed loop, to the public entry
``checker.linearizable(algorithm="tpu").check(None, model, history, {})``
in whole cycles of the pool, until ``--seconds`` have passed and the
cycle in flight has ended.
After the window the plain reference judges every verdict the window
produced.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import random
import shutil
import sys
import tempfile
import time

DEVICE_ANALYZERS = ("tpu-dense", "tpu-bfs")
SUPERVISION_EVENTS = ("event_wedge", "event_fault", "event_quarantine",
                      "event_static")
HOST_STAT_FAULTS = ("watchdog_trips", "faults", "quarantine_skips",
                    "static_skips", "cpu_rows")


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# --- finding things by name ------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{path}: no such {name}")
    key = f"_bench_{name}_{abs(hash(path))}"
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod        # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: list          # metric entries of BENCHMARK.json, this cell's


def find_cell(root: str, workload: str, trace: bool) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix and the metrics it reports: the
    end-to-end ones with ``trace`` off, the per-layer ones with it on."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "benchmark", "workloads",
                                     f"{w['traffic']}.json"))
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in bench[kind]
               if "workloads" not in m or workload in m["workloads"]]
    return Cell(workload, int(w["chips"]), config, traffic, metrics)


def reader(root: str, metric: str):
    return load_module(os.path.join(root, "benchmark", "metrics",
                                    f"{metric}.py"), f"metric_{metric}")


def generator(root: str, config: dict):
    return load_module(os.path.join(root, "benchmark", "traffic",
                                    f"{config['generator']}.py"),
                       f"traffic_{config['generator']}")


def reference(root: str, config: dict):
    return load_module(os.path.join(root, "benchmark", "reference",
                                    f"{config['reference']}.py"),
                       f"reference_{config['reference']}")


# --- the device --------------------------------------------------------------

def device_info(chips: int) -> dict:
    """The chips this run uses, as JAX reports them; raises
    :class:`NoAccelerator` unless they are TPUs, enough of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees "
                            f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int):
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# --- spans -------------------------------------------------------------------

class Spans:
    """Host spans the benchmark records around the calls into each layer.
    Each is also a ``jax.profiler.TraceAnnotation`` named
    ``bench.<span>`` when tracing, so that host and device activity
    share one clock. Seconds are summed only while ``active``."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.active = False
        self.seconds: dict = {}

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.trace:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            if self.active:
                self.seconds[name] = self.seconds.get(name, 0.0) + \
                    time.perf_counter() - t0

    def wrap(self, module, attr: str, name: str) -> None:
        """Put ``module.attr`` inside span ``name`` (the pattern of
        chip_smoke.py's ``_PallasCount``)."""
        real = getattr(module, attr)
        real = getattr(real, "__wrapped__", real)     # one wrap at a time
        spans = self

        def spanned(*a, **kw):
            with spans.span(name):
                return real(*a, **kw)

        spanned.__wrapped__ = real
        setattr(module, attr, spanned)


def install_layer_spans(spans: Spans) -> None:
    """Spans around the host pack and the witness tail replay."""
    from jepsen_tpu.lin import prepare, witness

    spans.wrap(prepare, "prepare", "pack")
    spans.wrap(witness, "tail_replay", "witness")
    spans.wrap(witness, "tail_replay_sparse", "witness")


# --- traffic -----------------------------------------------------------------

@dataclasses.dataclass
class Item:
    """One history of the pool."""

    gen: object            # the generator's record (events, lin, corrupted)
    history: object        # the checker's History
    ops: int               # invocations
    rows: int              # return rows: ok completions
    expected: dict | None = None


def to_history(events: list):
    from jepsen_tpu.history import History, Op

    return History([Op(t, f, v, p, index=i)
                    for i, (t, f, v, p) in enumerate(events)])


MAX_DRAWS = 200       # generator draws a pool may take to fill its strata


def build_pool(gen_mod, config: dict, traffic: dict, seed: int,
               transform=None) -> list:
    """The cell's pool, drawn from ``seed``.

    ``strata`` (``{property: [value, ...]}``): one history for each
    listed value, the first the seed's stream yields with that value of
    ``gen_mod.strata``: every seed gets the same set of sizes, in
    another order. ``corrupt`` (optional) adds, for each ``of[j]`` (a
    position in that list), a copy of that history with one ok read
    corrupted: the first at or after return row ``align_rows *
    floor(at[j] * rows / align_rows)`` plus a seeded offset below
    ``within``. ``transform`` (a control) rewrites each history's
    events before the checker sees them."""
    rng = random.Random(seed)
    (key, want), = traffic["strata"].items()
    gens = [None] * len(want)
    for _ in range(MAX_DRAWS):
        open_ = [want[i] for i, g in enumerate(gens) if g is None]
        if not open_:
            break
        g = gen_mod.generate(seed=rng.getrandbits(64),
                             accept=lambda st: st[key] in open_,
                             **config["history"])
        if g is not None:
            v = gen_mod.strata(g)[key]
            gens[next(i for i, h in enumerate(gens)
                      if h is None and want[i] == v)] = g
    if any(g is None for g in gens):
        raise RuntimeError(f"seed {seed}: {key} strata not filled within "
                           f"{MAX_DRAWS} draws")
    bad = traffic.get("corrupt")
    if bad:
        align = int(bad["align_rows"])
        for i, q in zip(bad["of"], bad["at"]):
            g = gens[i]
            row = align * int(q * gen_mod.rows(g) / align) + \
                rng.randrange(int(bad["within"]))
            gens.append(gen_mod.corrupt(g, row))
    pool = []
    for g in gens:
        events = transform(g.events) if transform else g.events
        pool.append(Item(g, to_history(events),
                         sum(1 for e in g.events if e[0] == "invoke"),
                         gen_mod.rows(g)))
    rng.shuffle(pool)
    return pool


# --- one call ----------------------------------------------------------------

@dataclasses.dataclass
class Call:
    item: int
    t0: float
    t1: float
    ops: int
    rows: int
    result: dict
    failed: str | None


def make_model(config: dict):
    from jepsen_tpu import models

    return getattr(models, config["checker_model"])()


def decide(item: Item, model, k: int, spans: Spans) -> Call:
    """One closed-loop request through the public entry, with what the
    run needs to know of it: its verdict and whether it failed."""
    from jepsen_tpu import checker
    from jepsen_tpu.obs import metrics

    reg = metrics.REGISTRY
    ev0 = dict(reg.snapshot().get("counters", {}))
    failed = None
    t0 = time.perf_counter()
    try:
        with spans.span("call"):
            r = checker.linearizable(algorithm="tpu").check(
                None, model, item.history, {})
    except Exception as e:  # noqa: BLE001 - a failed request, recorded
        r = {"valid?": "unknown", "error": repr(e)}
    t1 = time.perf_counter()
    ev1 = reg.snapshot().get("counters", {})
    if r.get("valid?") not in (True, False):
        failed = f"verdict {r.get('valid?')!r}: {r.get('error')}"
    elif r.get("analyzer") not in DEVICE_ANALYZERS:
        failed = f"decided off the device by {r.get('analyzer')!r}"
    else:
        bad = {k_: (r.get("host-stats") or {}).get(k_)
               for k_ in HOST_STAT_FAULTS}
        bad = {k_: v for k_, v in bad.items() if v}
        bad.update({k_: ev1.get(k_, 0) - ev0.get(k_, 0)
                    for k_ in SUPERVISION_EVENTS
                    if ev1.get(k_, 0) != ev0.get(k_, 0)})
        if bad:
            failed = f"supervision: {bad}"
    return Call(k, t0, t1, item.ops, item.rows, r, failed)


# --- the comparison ----------------------------------------------------------

def judge(calls: list, pool: list, ref_mod) -> dict:
    """Each number compared, with its limit: wrong or missing verdicts,
    a wrong named op, and counterexample paths that are not legal runs
    of the history. The reference runs here, after the window."""
    wrong = missing = wrong_op = bad_paths = 0
    for c in calls:
        item = pool[c.item]
        if item.expected is None:
            g = item.gen
            item.expected = ref_mod.expected(g.events, g.lin, g.corrupted)
        want = item.expected
        got = c.result.get("valid?")
        if got not in (True, False):
            missing += 1
            continue
        if got is not want["valid?"]:
            wrong += 1
            continue
        if not want["valid?"]:
            if ref_mod.op_mismatch(c.result.get("op"), want["op"]):
                wrong_op += 1
            if ref_mod.paths_fault(item.gen.events,
                                   c.result.get("final-paths")):
                bad_paths += 1
    return {"wrong_verdicts": {"value": wrong, "limit": 0},
            "missing_verdicts": {"value": missing, "limit": 0},
            "wrong_named_ops": {"value": wrong_op, "limit": 0},
            "bad_counterexamples": {"value": bad_paths, "limit": 0}}


# --- the run -----------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    setup_s: float
    window_s: float
    calls: list
    spans: dict
    compiles_in_window: int
    trace: object = None          # trace.reduce.TraceSummary, traced runs
    device: dict = dataclasses.field(default_factory=dict)

    @property
    def decided(self) -> list:
        return [c for c in self.calls if c.failed is None]


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def measure(root: str, workload: str, seed: int, seconds: float,
            trace: bool, t_start: float, transform=None,
            require_tpu: bool = True) -> dict:
    """One run of a cell; returns the result line as a dict. Raises
    :class:`NoAccelerator` before any work when ``require_tpu`` and
    there is no chip for the cell."""
    cell = find_cell(root, workload, trace)
    gen_mod = generator(root, cell.config)
    ref_mod = reference(root, cell.config)
    readers = {m["name"]: reader(root, m["name"]) for m in cell.metrics}

    os.environ["JEPSEN_TPU_QUARANTINE"] = "0"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                           ".jax_cache")
    import jax

    device = device_info(cell.chips) if require_tpu else {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": cell.chips}
    from benchmark.trace import peaks, reduce as treduce
    from jepsen_tpu import util

    if require_tpu:
        peaks.peaks(device["kind"])
    util.install_compile_meter()
    util.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    spans = Spans(trace)
    install_layer_spans(spans)
    pool = build_pool(gen_mod, cell.config, cell.traffic, seed, transform)
    model = make_model(cell.config)
    for k, item in enumerate(pool):            # warm-up: every shape used
        decide(item, model, k, spans)
    gc.collect()
    gc.freeze()

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    summary = None
    try:
        if trace:
            jax.profiler.start_trace(tdir, profiler_options=_profile_options())
        c0 = util.compile_meter()["xla_compiles"]
        calls = []
        spans.active = True
        w0 = time.perf_counter()
        setup_s = time.monotonic() - t_start
        with spans.span("window"):
            # Whole cycles of the pool: every run decides each history
            # equally often, whichever order its seed drew.
            k = 0
            while True:
                calls.append(decide(pool[k % len(pool)], model,
                                    k % len(pool), spans))
                k += 1
                if k % len(pool) == 0 and calls[-1].t1 - w0 >= seconds:
                    break
        window_s = calls[-1].t1 - w0
        spans.active = False
        compiles = util.compile_meter()["xla_compiles"] - c0
        if trace:
            jax.profiler.stop_trace()
            summary = treduce.reduce(treduce.load(tdir))
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)
    if summary is not None:
        device["busy_s"] = summary.busy_ns / 1e9
        device["window_s"] = summary.window_ns / 1e9

    run = Run(cell, setup_s, window_s, calls, dict(spans.seconds),
              compiles, summary, device)
    metrics = {}
    for m in cell.metrics:
        v = readers[m["name"]].read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = judge(calls, pool, ref_mod)
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": len(calls),
           "failed": sum(1 for c in calls if c.failed is not None),
           "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = summary.breakdown()
    out["checks"] = checks
    failures = [c.failed for c in calls if c.failed]
    if failures:
        print(f"failed histories: {failures[:5]}", file=sys.stderr)
    return out
