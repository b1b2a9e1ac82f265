"""Reduction of a jax.profiler trace (.xplane.pb) to the numbers the
benchmark reports. Kept with the benchmark so every PR computes them
the same way.

- busy: the union of the intervals in which an operation ran on a
  device (the device plane's op line), averaged over the devices used;
- device time by program: summed durations of the events on the device
  plane's module line, grouped by the program's name;
- idle gaps: the longest intervals with nothing on the device, each
  named by the host annotation that covered most of it.

`reduce_events` works on plain tuples, so a recorded trace kept as JSON
checks it without JAX; `reduce_dir` reads the profiler's file.
"""
from __future__ import annotations

import glob
import os
import re

OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = re.compile(r"^/host:")


def union(intervals):
    """Merged, sorted intervals and their total length."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged, sum(e - s for s, e in merged)


def program_name(event_name: str) -> str:
    """`jit_fn(123456789)` and `jit_fn` are one program."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def reduce_events(planes: dict, programs: list, t0_ns=None, t1_ns=None,
                  top: int = 10) -> dict:
    """`planes`: {plane name: {line name: [(name, start_ns, dur_ns)]}}.
    `programs`: regular expressions; a module event whose program name
    matches one counts towards `solve_ns`. The window is [t0_ns, t1_ns],
    by default the span of the device's and the host's events."""
    dev = {p: lines for p, lines in planes.items() if DEVICE_PLANE.match(p)}
    host = {p: lines for p, lines in planes.items() if HOST_PLANE.match(p)}
    pats = [re.compile(p) for p in programs]
    op_iv, per_dev_busy, by_prog, by_op = [], [], {}, {}
    solve_ns, solve_n = 0, 0
    for lines in dev.values():
        ivs = []
        for ln in OP_LINES:
            for name, s, d in lines.get(ln, ()):
                ivs.append((s, s + d))
                by_op[name] = by_op.get(name, 0) + d
        if not ivs:     # a backend without an op line: modules are the ops
            for ln in MODULE_LINES:
                ivs.extend((s, s + d) for _n, s, d in lines.get(ln, ()))
        for ln in MODULE_LINES:
            for name, s, d in lines.get(ln, ()):
                prog = program_name(name)
                row = by_prog.setdefault(prog, [0, 0])
                row[0] += 1
                row[1] += d
                if any(p.search(prog) for p in pats):
                    solve_ns += d
                    solve_n += 1
        if ivs:
            op_iv.append(ivs)
    if not op_iv:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0,
                "programs": {}, "solve_s": 0.0, "solve_events": 0,
                "device_ops": [], "idle_gaps": []}
    # what the host was doing, for naming the gaps; the traced window is
    # from the first to the last event of either kind
    notes = []
    for lines in host.values():
        for evs in lines.values():
            notes.extend((n, s, s + d) for n, s, d in evs)
    starts = [s for ivs in op_iv for s, _e in ivs] + [s for _n, s, _e in notes]
    ends = [e for ivs in op_iv for _s, e in ivs] + [e for _n, _s, e in notes]
    lo = min(starts) if t0_ns is None else t0_ns
    hi = max(ends) if t1_ns is None else t1_ns
    gaps = []
    for ivs in op_iv:
        merged, _ = union([(max(s, lo), min(e, hi)) for s, e in ivs
                           if e > lo and s < hi])
        per_dev_busy.append(sum(e - s for s, e in merged))
        edge = lo
        for s, e in merged:
            if s > edge:
                gaps.append((edge, s))
            edge = e
        if hi > edge:
            gaps.append((edge, hi))
    by_host: dict = {}
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        best, cover = "unattributed", 0
        for n, hs, he in notes:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = n, c
        by_host[best] = by_host.get(best, 0) + (e - s)
    return {
        "devices": len(op_iv),
        "busy_s": sum(per_dev_busy) / len(per_dev_busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "programs": {k: [v[0], v[1] / 1e9] for k, v in by_prog.items()},
        "solve_s": solve_ns / 1e9,
        "solve_events": solve_n,
        "device_ops": [[k[:120], v / 1e9] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(
            by_host.items(), key=lambda kv: -kv[1])[:top]],
    }


def read_planes(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes: dict = {}
    for plane in data.planes:
        if not (DEVICE_PLANE.match(plane.name) or HOST_PLANE.match(plane.name)):
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return planes


def keep_host_annotations(planes: dict, labels: set) -> dict:
    """Host planes carry every Python frame the tracer saw; only the
    benchmark's own annotations name a gap."""
    out = {}
    for p, lines in planes.items():
        if DEVICE_PLANE.match(p):
            out[p] = lines
        else:
            out[p] = {ln: [ev for ev in evs if ev[0] in labels]
                      for ln, evs in lines.items()}
    return out


def reduce_dir(trace_dir: str, programs: list, labels: list) -> dict:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return reduce_events({}, programs)
    planes = keep_host_annotations(read_planes(files[-1]), set(labels))
    return reduce_events(planes, programs)
