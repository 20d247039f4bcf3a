"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reduces the JAX profiler's ``.xplane.pb`` to a small plain form:
for each device plane its lines of ``(name, start_ns, duration_ns)``
events, and the host spans the benchmark annotated.  Everything else works
on that form, so a test can feed it a recorded trace.

On a TPU the device planes are named ``/device:TPU:<n>``; the line
``XLA Ops`` holds one event per operation (a Pallas kernel appears under
its kernel name) and ``XLA Modules`` one per program execution
(``jit_<function>``).
"""
from __future__ import annotations

import gzip
import json
from pathlib import Path

OPS, MODULES = "XLA Ops", "XLA Modules"
# the host span that marks the traced stretch of the window
WINDOW_SPAN = "bench.trace_window"


def load(trace_dir: str, span_names: set[str]) -> dict:
    """The plain form of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            # an operation's event is named by its HLO instruction; keep
            # the name, not the shapes after it
            devices[plane.name] = {
                line.name: [(e.name.split(" = ")[0], int(e.start_ns),
                             int(e.duration_ns)) for e in line.events]
                for line in plane.lines if line.name in (OPS, MODULES)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns), int(e.duration_ns))
                             for e in line.events if e.name in span_names)
    return {"devices": devices, "spans": spans}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _busy_line(lines: dict) -> list:
    return lines.get(OPS) or lines.get(MODULES) or []


def busy_intervals(trace: dict, lo: int, hi: int) -> dict:
    """Per device that ran anything, the union of the intervals in which
    an operation ran, clipped to ``[lo, hi)`` ns."""
    out = {}
    for dev, lines in trace["devices"].items():
        if not _busy_line(lines):
            continue
        iv = [(max(s, lo), min(s + d, hi)) for _, s, d in _busy_line(lines)
              if s < hi and s + d > lo]
        out[dev] = _union(iv)
    return out


def busy_s(trace: dict, lo: int, hi: int) -> float:
    """Seconds of ``[lo, hi)`` with an operation running, averaged over
    the device planes.  None when the trace holds no device operation."""
    per = busy_intervals(trace, lo, hi)
    if not per or not any(per.values()):
        return None
    return sum(sum(e - s for s, e in iv) for iv in per.values()) \
        / len(per) / 1e9


def events(trace: dict, line: str, match: str) -> list[tuple[str, int, int]]:
    """Events of every device's ``line`` whose name contains ``match``."""
    return [e for lines in trace["devices"].values()
            for e in lines.get(line, []) if match in e[0]]


def device_seconds(trace: dict, line: str, match: str) -> tuple[float, int]:
    """Total device seconds and count of the matching events."""
    ev = events(trace, line, match)
    return sum(d for _, _, d in ev) / 1e9, len(ev)


def top_ops(trace: dict, k: int = 10) -> list[list]:
    """The ``k`` operation names that took most device time, seconds."""
    tot: dict[str, int] = {}
    for lines in trace["devices"].values():
        for name, _, d in lines.get(OPS, []):
            tot[name] = tot.get(name, 0) + d
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9] for n, d in top]


def idle_gaps(trace: dict, lo: int, hi: int, k: int = 10) -> list[list]:
    """The ``k`` longest stretches of ``[lo, hi)`` in which the first
    device ran nothing, each named by the host span that covered most of
    it (``"none"`` where no annotated span did)."""
    per = busy_intervals(trace, lo, hi)
    if not per:
        return []
    busy = per[sorted(per)[0]]
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        cover: dict[str, int] = {}
        for name, ss, dd in trace["spans"]:
            if name == WINDOW_SPAN:
                continue
            o = min(e, ss + dd) - max(s, ss)
            if o > 0:
                cover[name] = cover.get(name, 0) + o
        label = max(cover, key=cover.get) if cover else "none"
        out.append([label, (e - s) / 1e9])
    return out
