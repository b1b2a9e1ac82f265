"""Readers of metrics. A metric is a data file
(layer_metrics/<name>.json, end_to_end/<name>.json) that names one of
these readers and its arguments; a reader takes what the run gathered and returns the number,
or None when there was nothing to read (the harness then leaves the
metric out of the line; a share is never reported as 0 for want of
data).

`run` holds: snap0/snap1 (the server's counters, timer and gauge totals
and span sums at the window's edges), records (the generator's per-job
records, window jobs only), trace (tracered's reduction, traced runs),
config (the configuration file), device (as JAX reports it), window (its
edges, the allocs seen run at each, the set-up's seconds).
"""
from __future__ import annotations

import ast
import json
import operator
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _delta_pair(run, table: str, name: str):
    a = run["snap0"][table].get(name, [0, 0.0])
    b = run["snap1"][table].get(name)
    if b is None:
        return 0, 0.0
    return b[0] - a[0], b[1] - a[1]


def _counter(run, name: str) -> int:
    return (run["snap1"]["counters"].get(name, 0)
            - run["snap0"]["counters"].get(name, 0))


def timer_mean(args, run):
    """Sum of the named timers' totals over the window, per event of the
    first."""
    total, n_first = 0.0, None
    for name in args["timers"]:
        n, t = _delta_pair(run, "timers", name)
        total += t
        if n_first is None:
            n_first = n
    return total / n_first if n_first else None


def gauge_mean(args, run):
    n, t = _delta_pair(run, "gauges", args["gauge"])
    return t / n if n else None


def counter_ratio(args, run):
    den = sum(_counter(run, c) for c in args["per"])
    if not den:
        return None
    num = sum(_counter(run, c) for c in args["counters"])
    return args.get("scale", 1.0) * num / den


def counter_delta(args, run):
    if args.get("table") == "compile":
        return (run["snap1"]["compile"][args["key"]]
                - run["snap0"]["compile"][args["key"]])
    return _counter(run, args["counter"])


def span_sum(args, run):
    """Sum of the named spans' durations over the window, per span of
    the first name."""
    total, n_first = 0.0, None
    for name in args["spans"]:
        n, t = _delta_pair(run, "spans", name)
        total += t
        if n_first is None:
            n_first = n
    return total / n_first if n_first else None


def client_clock(args, run):
    """A statistic (mean or pNN) of one field of the generator's records
    of the window's jobs."""
    vals = sorted(r[args["field"]] for r in run["records"]
                  if r.get(args["field"]) is not None)
    if not vals:
        return None
    if args["stat"] == "mean":
        return sum(vals) / len(vals)
    return percentile(vals, float(args["stat"].lstrip("p")))


def percentile(sorted_vals, pct: float):
    """Nearest rank: the smallest value with at least pct% at or below."""
    k = max(0, -(-len(sorted_vals) * pct // 100) - 1)
    return sorted_vals[int(min(k, len(sorted_vals) - 1))]


def placed_rate(args, run):
    """Allocs the submitters saw run between the window's edges, a
    second."""
    w = run["window"]
    return (w["seen1"] - w["seen0"]) / (w["t1"] - w["t0"])


def setup_seconds(args, run):
    return run["window"]["setup_s"]


def traced_dispatches(args, run):
    """Fused dispatches the program counted between the traced window's
    edges (events of the `per_timer` timer)."""
    edges = run["window"].get("traced")
    if not edges:
        return 0
    name = args["per_timer"]
    return (edges[1]["timers"].get(name, [0])[0]
            - edges[0]["timers"].get(name, [0])[0])


def trace_device_time(args, run):
    """Device time of every solve program in the trace, per fused
    dispatch the program counted while it was taken, in ms."""
    tr = run.get("trace")
    n = traced_dispatches(args, run)
    if not tr or not tr.get("solve_s") or not n:
        return None
    return tr["solve_s"] * 1e3 / n


def trace_idle(args, run):
    tr = run.get("trace")
    if not tr or not tr.get("window_s") or not tr.get("busy_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


_OPS = {ast.Add: operator.add, ast.Sub: operator.sub,
        ast.Mult: operator.mul, ast.Div: operator.truediv}


def evaluate(expr: str, symbols: dict) -> float:
    """Arithmetic over named shapes: + - * / and parentheses only."""
    def walk(node):
        if isinstance(node, ast.BinOp) and type(node.op) in _OPS:
            return _OPS[type(node.op)](walk(node.left), walk(node.right))
        if isinstance(node, ast.Constant) and isinstance(
                node.value, (int, float)):
            return node.value
        if isinstance(node, ast.Name):
            return symbols[node.id]
        raise ValueError(f"not arithmetic over shapes: {expr!r}")
    return float(walk(ast.parse(expr, mode="eval").body))


def model_bytes(model: dict, symbols: dict) -> float:
    """Bytes a kernel must move whatever implements it: each operand
    read once, each result written once."""
    return model["dtype_bytes"] * sum(
        evaluate(term["elements"], symbols) for term in model["terms"])


def peak(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in perfbench/peaks.json")
    return table[device_kind]


def roofline(args, run):
    """Share of the HBM roofline: the least time the chip could take to
    move one fused solve's operands and results once, over the device
    time every solve program took per fused dispatch (the fixpoint's
    small re-solves run under the same name and count in the time, not
    in the bytes: the share is a lower bound). Bound by bandwidth by
    construction."""
    per_dispatch_ms = trace_device_time(args, run)
    if per_dispatch_ms is None:
        return None
    solve = run["config"]["solve"]
    with open(os.path.join(HERE, "work_models",
                           f"{solve['work_model']}.json")) as f:
        model = json.load(f)
    symbols = dict(solve["symbols"])
    lanes = gauge_mean({"gauge": args["lanes_gauge"]}, run)
    if lanes is None:
        return None
    symbols["E"] = lanes
    hbm = peak(run["device"]["kind"])["hbm_bytes_per_s"]
    least_ms = model_bytes(model, symbols) / hbm * 1e3
    return 100.0 * least_ms / per_dispatch_ms


READERS = {f.__name__: f for f in (
    timer_mean, gauge_mean, counter_ratio, counter_delta, span_sum,
    client_clock, placed_rate, setup_seconds, trace_device_time, trace_idle,
    roofline)}
