"""Reduction of a JAX profiler trace to device busy time, op times and idle gaps.

`load` reads the `.xplane.pb` the profiler wrote into a flat list of
events; `reduce` works on that list only, so it can be checked on a small
recorded trace.  The measured window is the host annotation `bench/window`
that the harness puts around it; every device interval is clipped to it.

* busy: the union of the intervals in which an op ran on a chip's
  "XLA Ops" line, averaged over the chips; idle share = 1 - busy / window.
* op time: the summed device duration per op name, with the op's HLO
  category where the trace gives one.
* idle gaps: each gap in a chip's busy union, tagged with what the host
  was doing at its midpoint: the innermost span the benchmark or the
  program opened, and the innermost host event of any kind.
"""
from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

WINDOW = "bench/window"
OPS_LINE = "XLA Ops"


class Ev(NamedTuple):
    plane: str
    line: str
    name: str
    start: float      # ns, on the profiler's clock
    dur: float        # ns
    stats: dict


def load(tdir: str) -> list[Ev]:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        return []
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            keep_stats = device and line.name == OPS_LINE
            for e in line.events:
                out.append(Ev(plane.name, line.name, e.name, float(e.start_ns),
                              float(e.duration_ns), dict(e.stats) if keep_stats else {}))
    return out


def union(intervals) -> list[tuple[float, float]]:
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def op_label(e: Ev) -> str:
    """The op's name, cut to its HLO instruction's head (a Pallas call's
    text carries its whole kernel), with its category where the trace has one."""
    cat = e.stats.get("hlo_category") or e.stats.get("category")
    name = e.name[:200]
    return f"{name} [{cat}]" if cat else name


class Reduced:
    def __init__(self, window, planes, ops_by_plane, host):
        self.t0, self.t1 = window
        self.window_s = (self.t1 - self.t0) * 1e-9
        self.planes = planes
        self.ops = ops_by_plane                 # plane -> [Ev] clipped to the window
        self.host = host                        # host events inside the window
        busy = [sum(b - a for a, b in union((e.start, e.start + e.dur) for e in self.ops[p]))
                for p in planes]
        self.busy_s = (sum(busy) / len(busy)) * 1e-9 if busy else 0.0

    def op_events(self):
        return [e for p in self.planes for e in self.ops[p]]

    def op_seconds(self) -> dict:
        out: dict = {}
        for e in self.op_events():
            k = op_label(e)
            out[k] = out.get(k, 0.0) + e.dur * 1e-9 / len(self.planes)
        return out

    def gaps(self, longest: int = 50) -> list[tuple[str, float]]:
        """The `longest` gaps of the first chip's busy union, tagged."""
        if not self.planes:
            return []
        busy = union((e.start, e.start + e.dur) for e in self.ops[self.planes[0]])
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        spans = sorted(((a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a),
                       key=lambda ab: ab[0] - ab[1])[:longest]
        return [(self.tag((a + b) / 2), (b - a) * 1e-9) for a, b in spans]

    def tag(self, t: float) -> str:
        cover = [e for e in self.host if e.start <= t <= e.start + e.dur and e.name != WINDOW]
        spans = [e for e in cover if "/" in e.name and not e.name.startswith("$")]
        inner = min(cover, key=lambda e: e.dur).name if cover else "no host event"
        span = min(spans, key=lambda e: e.dur).name if spans else "no span"
        return f"{span} | {inner}"[:160]

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:10]
        gaps: dict = {}
        for tag, s in self.gaps():
            gaps.setdefault(tag, []).append(s)
        longest = sorted(((tag, max(v)) for tag, v in gaps.items()), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in longest[:10]]}


def device_plane(name: str, ids) -> bool:
    m = re.fullmatch(r"/device:TPU:(\d+)", name)
    return bool(m) and m.group(1) in ids


def reduce(events: list[Ev], device_ids) -> Reduced | None:
    """None when the trace holds no window annotation or no device op."""
    ids = {str(i) for i in device_ids}
    wins = [e for e in events if e.name == WINDOW and not e.plane.startswith("/device:")]
    if not wins:
        return None
    w = max(wins, key=lambda e: e.dur)
    t0, t1 = w.start, w.start + w.dur
    planes = sorted({e.plane for e in events if device_plane(e.plane, ids)})
    ops = {p: [] for p in planes}
    for e in events:
        if e.plane in ops and e.line == OPS_LINE and e.start < t1 and e.start + e.dur > t0:
            a, b = max(e.start, t0), min(e.start + e.dur, t1)
            ops[e.plane].append(e._replace(start=a, dur=b - a))
    if not any(ops.values()):
        return None
    host = [e for e in events if e.plane.startswith("/host:") and e.dur > 0
            and e.start < t1 and e.start + e.dur > t0]
    return Reduced((t0, t1), planes, ops, host)


def dump(events: list[Ev], limit: int = 60) -> dict:
    """What a trace holds, for reading one by hand: planes and lines with
    their event counts, the costliest event names per device line, and the
    stats of a few events of each kind."""
    lines: dict = {}
    for e in events:
        d = lines.setdefault((e.plane, e.line), {"n": 0, "names": {}, "examples": {}})
        d["n"] += 1
        key = re.sub(r"\.\d+$", "", e.name)
        d["names"][e.name] = d["names"].get(e.name, 0.0) + e.dur * 1e-9
        if e.stats and key not in d["examples"] and len(d["examples"]) < limit:
            d["examples"][key] = {k: str(v)[:400] for k, v in e.stats.items()}
    out = {}
    for (plane, line), d in lines.items():
        top = sorted(d["names"].items(), key=lambda kv: -kv[1])[:limit]
        calls = sorted((kv for kv in d["names"].items() if "custom-call(" in kv[0][:400]),
                       key=lambda kv: -kv[1])[:limit]
        out[f"{plane} :: {line}"] = {"events": d["n"], "top_s": top, "examples": d["examples"],
                                     "custom_calls": [(n[:300], s) for n, s in calls]}
    return out
