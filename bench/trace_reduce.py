"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports.

Read with `jax.profiler.ProfileData`, nothing else.  A device is a plane
named `/device:<platform>:<n>`; its operations are the events of its
`XLA Ops` line.  The host spans are the benchmark's own annotations
(`bench.*`, `jax.profiler.TraceAnnotation`) on the host planes.  All
times are on the trace's one clock.

* busy: the union of the intervals in which an operation ran, per device,
  averaged over the devices;
* window: from the first to the last event of the trace;
* op classes: device time by the operation's HLO category (its
  `hlo_category` statistic, else its kind, `op_kind`);
* device ops: the ten operations with the most device time;
* idle gaps: the ten longest intervals of device 0 with no operation
  running, each named by the innermost benchmark span open on the host at
  its midpoint (`host idle` where none is).
"""

from __future__ import annotations

import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
TOP = 10


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except (TypeError, ValueError):
        return {}


def op_kind(name: str) -> str:
    """The kind of an operation from its event name, which on a TPU is the
    HLO instruction's text (`%fusion.5 = f32[...] fusion(...), kind=kCustom
    ...`): the instruction name without '%' and its number, with a
    fusion's kind."""
    head = name.split(" = ", 1)[0].lstrip("%")
    kind = re.sub(r"\.\d+$", "", head)
    m = re.search(r"kind=(k\w+)", name)
    return f"{kind} {m.group(1)}" if m else kind


def short(name: str, width: int = 160) -> str:
    return name if len(name) <= width else name[: width - 3] + "..."


def union_length(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float):
    """The uncovered [start, end) pieces of [lo, hi]."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def collect(profile) -> dict:
    """{'devices': {plane: [(start_s, end_s, name)]}, 'op_stats': {name:
    stats of its first event}, 'spans': [(start_s, end_s, name)],
    'first_s', 'last_s'}."""
    devices, spans, op_stats = {}, [], {}
    first, last = float("inf"), float("-inf")
    for plane in profile.planes:
        is_device = plane.name.startswith("/device:") and \
            not plane.name.startswith("/device:CUSTOM")
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if is_device and line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).append(
                        (s, e, ev.name))
                    if ev.name not in op_stats:
                        op_stats[ev.name] = _stats(ev)
                elif not is_device and ev.name.startswith(SPAN_PREFIX):
                    spans.append((s, e, ev.name))
                else:
                    continue
                first, last = min(first, s), max(last, e)
    return {"devices": devices, "op_stats": op_stats, "spans": spans,
            "first_s": first, "last_s": last}


def reduce_collected(c: dict, chips: int) -> dict:
    if not c["devices"]:
        raise ValueError("the trace holds no device operations")
    planes = sorted(c["devices"])[:chips]
    lo, hi = c["first_s"], c["last_s"]
    busy = [union_length([(s, e) for s, e, _ in c["devices"][p]])
            for p in planes]
    op_time, op_class = defaultdict(float), defaultdict(float)
    op_count = defaultdict(int)
    for p in planes:
        for s, e, name in c["devices"][p]:
            op_time[name] += (e - s) / len(planes)
            op_count[name] += 1
    op_info = {n: {k: v for k, v in c["op_stats"].get(n, {}).items()
                   if isinstance(v, str)} for n in op_time}
    for n, t in op_time.items():
        op_class[str(op_info[n].get("hlo_category") or op_kind(n))] += t
    first_dev = c["devices"][planes[0]]
    idle = sorted(gaps([(s, e) for s, e, _ in first_dev], lo, hi),
                  key=lambda g: g[0] - g[1])
    host_spans = defaultdict(float)
    for s, e, name in c["spans"]:
        host_spans[name] += e - s

    def label(mid):
        open_ = [(s, name) for s, e, name in c["spans"] if s <= mid < e]
        return max(open_)[1] if open_ else "host idle"

    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": hi - lo,
        "op_classes": dict(sorted(op_class.items(), key=lambda kv: -kv[1])),
        "device_ops": [[short(n), t] for n, t in top_ops],
        "idle_gaps": [[label((s + e) / 2), e - s] for s, e in idle[:TOP]],
        "host_spans": dict(host_spans),
        "ops": {n: {"s": op_time[n], "count": op_count[n] // len(planes),
                    **op_info[n]} for n in op_time},
    }


def reduce(path: str, chips: int) -> dict:
    """The reduction of the trace file at `path` over its first `chips`
    devices."""
    return reduce_collected(collect(load(path)), chips)
