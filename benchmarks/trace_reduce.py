"""From a profiler trace to numbers: device busy union and idle share,
device time by XLA module and op, collectives' time, the longest idle
gaps and what the host was doing in them.

The reduction works on a plain form of the trace, so that it can be
checked on a small recorded cut (``testdata/``) without the profiler:

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

``load_xplane`` makes that form from the ``.xplane.pb`` the jax profiler
writes. On a TPU each chip is a plane ``/device:TPU:<n>`` whose line
``XLA Ops`` holds one event per executed HLO op and whose line ``XLA
Modules`` one per executed program (``jit_<function>(<fingerprint>)``);
host threads are lines of the plane ``/host:CPU``, where the
benchmark's own ``TraceAnnotation``s (``bench.*``) land.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# HLO opcodes that move data between chips (with their async halves)
COLLECTIVE = re.compile(
    r"(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)(-start|-done)?")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, keep_host=lambda name: name.startswith("bench.")) -> dict:
    """The plain form: every device plane whole; of the host planes only
    the events `keep_host` accepts (the host tracer records far more)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            ev = [[e.name, int(e.start_ns), int(e.duration_ns)]
                  for e in line.events if is_dev or keep_host(e.name)]
            if ev:
                lines.append({"name": line.name, "events": ev})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list:
    out = [(int(DEVICE_PLANE.match(p["name"]).group(1)), p)
           for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return [p for _i, p in sorted(out, key=lambda t: t[0])]


def line_events(plane: dict, line_name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def host_spans(trace: dict, prefix: str = "bench.") -> list:
    """[name, start_ns, duration_ns] of the benchmark's own annotations."""
    out = []
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for line in p["lines"]:
            out += [e for e in line["events"] if e[0].startswith(prefix)]
    return sorted(out, key=lambda e: e[1])


def clip(events: list, t0: int, t1: int) -> list:
    """Events cut to [t0, t1): (name, start, end)."""
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b))
    return out


def union(intervals: list) -> list:
    """Merged [start, end) intervals of (name, start, end) events."""
    merged = []
    for _n, a, b in sorted(intervals, key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(events: list, t0: int, t1: int) -> int:
    return sum(b - a for a, b in union(clip(events, t0, t1)))


def top_level(intervals: list) -> list:
    """Drops events nested inside an earlier, enclosing one (a while or
    call op's body ops are events of their own inside the parent's
    interval): what is left can be summed without counting time twice."""
    out, end = [], -1
    for ev in sorted(intervals, key=lambda e: (e[1], -(e[2] - e[1]))):
        if ev[1] >= end:
            out.append(ev)
            end = ev[2]
    return out


def gaps(events: list, t0: int, t1: int) -> list:
    """Idle [start, end) stretches of [t0, t1) in which no op ran."""
    out, at = [], t0
    for a, b in union(clip(events, t0, t1)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if t1 > at:
        out.append((at, t1))
    return out


def _module_of(modules: list):
    """Maps an instant to the program that was running: modules on one
    chip run one after another."""
    import bisect

    mods = sorted(modules, key=lambda e: e[1])
    starts = [m[1] for m in mods]

    def find(t):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < mods[i][2]:
            return mods[i][0]  # with its fingerprint: programs of one name differ
        return "(no module)"
    return find


def opcode(name: str) -> str:
    """The HLO opcode of an op event. The profiler names an op by its
    whole HLO line, '%all_to_all.61 = u32[4,1,750152]{...} all-to-all(...)':
    the opcode is the word before the operands, not the instruction's
    name (jax's `all_to_all` also names the reshapes around the exchange).
    A bare name ('fusion.3') is its own opcode, less the numbering."""
    _head, sep, rest = name.partition(" = ")
    if not sep:
        return re.sub(r"[.\d]+$", "", name.lstrip("%"))
    kind = re.search(r"\s([a-z][\w\-]*)\(", " " + rest)
    return kind.group(1) if kind else ""


def short_op(name: str) -> str:
    """'%fusion.875 = u32[12002430]{0:T(1024)} fusion(...)' as
    '%fusion.875 u32[12002430] fusion'."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    return f"{head} {rest.split('{')[0][:40]} {opcode(name)}".strip()


def _host_label(spans: list, a: int, b: int) -> str:
    """What the host was doing over a gap, as far as the benchmark's own
    annotations say: the annotation covering most of it."""
    best, cover = "no statement in flight", 0
    for name, s, d in spans:
        ov = min(b, s + d) - max(a, s)
        if ov > cover:
            best, cover = "host, inside " + name, ov
    return best


def reduce_trace(trace: dict, t0: int, t1: int, spans=None, top: int = 10) -> dict:
    """Everything the per-layer readers use, over the span [t0, t1) of
    the trace's clock (ns). `spans` ([name, start_ns, duration_ns] on the
    trace's clock) say what the host was doing, for the gaps; by default
    the ``bench.query`` annotations the trace itself holds."""
    if spans is None:
        spans = host_spans(trace, "bench.query")
    per_device, op_time, gap_list = [], {}, []
    for plane in device_planes(trace):
        ops = top_level(clip(line_events(plane, OPS_LINE), t0, t1))
        module_of = _module_of(clip(line_events(plane, MODULES_LINE), t0, t1))
        busy = sum(b - a for a, b in union(ops))
        coll = sum(b - a for n, a, b in ops if COLLECTIVE.fullmatch(opcode(n)))
        per_device.append({"plane": plane["name"], "busy_ns": busy,
                           "op_ns": sum(b - a for _n, a, b in ops),
                           "collective_ns": coll, "ops": len(ops)})
        by_mod = {}
        for n, a, b in ops:
            m = module_of(a)
            by_mod[m] = by_mod.get(m, 0) + (b - a)
            key = f"{m}/{short_op(n)}"
            op_time[key] = op_time.get(key, 0) + (b - a)
        per_device[-1]["by_module_ns"] = by_mod
        ops_as_events = [[n, a, b - a] for n, a, b in ops]
        gap_list.append([(b - a, a, b) for a, b in gaps(ops_as_events, t0, t1)])
    n_dev = max(1, len(per_device))
    gap_by = {}
    for length, a, b in (g for dev in gap_list for g in dev):
        label = _host_label(spans, a, b)
        gap_by[label] = gap_by.get(label, 0) + length
    # the longest gaps of the busiest chip (the one device_idle_pct reads):
    # chips of one mesh idle together, and ten entries are few
    busiest = max(range(len(per_device)), key=lambda i: per_device[i]["busy_ns"],
                  default=None)
    longest = sorted(gap_list[busiest], reverse=True)[:top] if gap_list else []
    return {
        "span_ns": t1 - t0, "devices": per_device,
        "busy_ns_mean": sum(d["busy_ns"] for d in per_device) / n_dev,
        "busy_ns_max": max((d["busy_ns"] for d in per_device), default=0),
        "op_ns_mean": sum(d["op_ns"] for d in per_device) / n_dev,
        "collective_ns_mean": sum(d["collective_ns"] for d in per_device) / n_dev,
        # averaged over the chips, so that the seconds are one chip's
        "device_ops": [[k, v / n_dev / 1e9] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_label(spans, a, b), length / 1e9]
                      for length, a, b in longest],
        "idle_by_host_s": {k: v / n_dev / 1e9 for k, v in gap_by.items()},
    }


def cut(trace: dict, t0: int, t1: int) -> dict:
    """A cut of a trace to [t0, t1): what the recorded fixture is."""
    planes = []
    for p in trace["planes"]:
        lines = []
        for line in p["lines"]:
            ev = [[n, a, b - a] for n, a, b in clip(line["events"], t0, t1)]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}
