"""Reduction of one ``jax.profiler`` trace to the benchmark's numbers.

The trace is read as ``jax.profiler.ProfileData`` (an XSpace). Device
planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation that ran on the device. The benchmark writes its own
spans on the host plane as ``jax.profiler.TraceAnnotation`` events named
``bench.<span>``, on the same clock, and ``bench.window`` bounds the
measured window.

From these the reduction computes, inside the window:

- busy time: the union of the device-op intervals of each chip,
  averaged over the chips;
- device time by operation (instruction name and result shape), summed
  over the chips;
- idle gaps (the window less the busy union), each named by the
  innermost benchmark span open at its middle, or ``outside-spans``.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# An XLA Ops event is named by its HLO instruction's text:
# "%pallas_chunk.1 = (u32[128,128]{...}, ...) custom-call(...), ...".
_HLO = re.compile(r"(%[\w.\-]+) = \(?(\w+\[[\d,]*\])")


def op_name(name: str) -> str:
    """An operation's instruction name and first result shape
    (``%pallas_chunk.1 u32[128,128]``); other names as they are."""
    m = _HLO.match(name)
    return f"{m[1]} {m[2]}" if m else name


@dataclass
class TraceSummary:
    window_ns: float
    busy_ns: float                     # mean over chips
    chips: int
    op_ns: dict = field(default_factory=dict)     # name -> ns, all chips
    gap_ns: dict = field(default_factory=dict)    # span -> idle ns (mean)

    def kernel_ns(self, match) -> float:
        """Device ns of every operation whose name ``match`` accepts."""
        return sum(v for k, v in self.op_ns.items() if match(k))

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gap_ns.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v / 1e9] for k, v in top],
                "idle_gaps": [[k, v / 1e9] for k, v in gaps]}


def load(path: str):
    """``ProfileData`` from an ``.xplane.pb`` file, a directory holding
    one (the profiler's ``plugins/profile/<time>/``), or a text-format
    XSpace (``.pbtxt``, the test fixture)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if len(found) != 1:
            raise ValueError(f"{path}: want one .xplane.pb, found {found}")
        path = found[0]
    if path.endswith(".pbtxt"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def _union(iv: list) -> list:
    out: list = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def reduce(pd) -> TraceSummary:
    """The summary of the window that ``bench.window`` marks."""
    spans = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.start_ns, e.end_ns, e.name))
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"want one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0][:2]
    inner = [s for s in spans if s[2] != WINDOW_SPAN]
    devices = [p for p in pd.planes
               if p.name.startswith(DEVICE_PLANE_PREFIX)]
    op_ns: dict = {}
    gap_ns: dict = {}
    busy = 0.0
    for plane in devices:
        iv = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                a, b = _clip(e.start_ns, e.end_ns, lo, hi)
                if b <= a:
                    continue
                iv.append((a, b))
                name = op_name(e.name)
                op_ns[name] = op_ns.get(name, 0.0) + (b - a)
        u = _union(iv)
        busy += sum(b - a for a, b in u)
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                name = _innermost(inner, (a + b) / 2)
                gap_ns[name] = gap_ns.get(name, 0.0) + (b - a)
    n = max(len(devices), 1)
    return TraceSummary(window_ns=hi - lo, busy_ns=busy / n,
                        chips=len(devices), op_ns=op_ns,
                        gap_ns={k: v / n for k, v in gap_ns.items()})


def _innermost(spans: list, t: float) -> str:
    best = None
    for a, b, name in spans:
        if a <= t < b and (best is None or a >= best[0]):
            best = (a, b, name)
    return best[2][len(SPAN_PREFIX):] if best else "outside-spans"
