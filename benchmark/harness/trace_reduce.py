"""From a profiler trace (``*.xplane.pb``) to the numbers the metric
readers use. Only the process that holds the chip can take the trace;
this reduction runs on what `load_xplane` returns, a plain structure a
test can keep in the repo as JSON:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

What a v5e trace holds (looked at by hand, PR 24): one plane per chip,
``/device:TPU:<n>``; its line ``XLA Modules`` has one event per
execution of a jitted program, named ``jit_<function>(<fingerprint>)``;
its line ``XLA Ops`` has one event per executed HLO instruction, named
by the instruction's whole text (`short_name` cuts it down), a
``while`` enclosing the events of its body; ``Async XLA Ops`` holds
the ``*-start`` halves of asynchronous copies and is not counted as
busy. A Pallas kernel is a ``custom-call`` whose target is
``tpu_custom_call`` and whose ``kernel_metadata`` is empty: kernels
cannot be told apart by name today. The host's threads are lines of
the plane ``/host:CPU``; `jax.profiler.TraceAnnotation` events land on
the lines named ``python3``. Times are nanoseconds from the start of
the trace.

``python -m benchmark.harness.trace_reduce <trace dir or file>`` prints
a summary of any trace: the first thing to do with a new one.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)\Z")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")


_KIND = re.compile(r" ([a-z][a-z\-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text: str) -> str:
    """``%copy.96 = bf16[16,32]{1,0:T(8,128)} copy(bf16[16,32]{...} %p)``
    -> ``copy.96 copy bf16[16,32]``; a custom call also names its
    target. Anything that is not an instruction's text stays as it is."""
    head, eq, rest = text.partition(" = ")
    if not eq or not head.startswith("%"):
        return text
    kind, shape = _KIND.search(" " + rest), _SHAPE.search(rest)
    parts = [head[1:], kind.group(1) if kind else "?",
             shape.group(0) if shape else ""]
    target = _TARGET.search(rest)
    if target:
        parts.append(target.group(1))
    return " ".join(p for p in parts if p)


def find_xplane(path: str) -> str:
    """The newest ``*.xplane.pb`` under a profiler log directory."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return found[-1]


def load_xplane(path: str, keep_host: bool = True) -> dict:
    """Read a trace with nothing but JAX."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    planes = []
    for plane in data.planes:
        if not (DEVICE_PLANE.match(plane.name)
                or (keep_host and plane.name == HOST_PLANE)):
            continue
        lines = [{"name": line.name,
                  "events": [[short_name(e.name), float(e.start_ns),
                              float(e.duration_ns)] for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ------------------------------------------------------------- intervals

def union(intervals) -> list:
    """Merge [start, end) intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """The parts of the disjoint sorted intervals ``a`` not covered by
    the disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(events) -> list:
    """[(name, start, end, self_ns)] per event of ONE line, where a
    parent's self time leaves out the events nested inside it (a
    ``while`` and the instructions of its body)."""
    out, stack = [], []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][2] <= start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(end, stack[-1][2]) - start
        stack.append([name, start, end, dur])
    out.extend(tuple(s) for s in reversed(stack))
    return out


# ------------------------------------------------------------- reduction

def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(trace: dict) -> list:
    planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return sorted(planes, key=lambda p: int(
        DEVICE_PLANE.match(p["name"]).group(1)))


def cpu_stand_in(trace: dict) -> dict:
    """For a REHEARSAL only: the CPU backend has no device plane, so
    its executor threads' events stand in as device 0 and the traced
    path can be walked end to end without a chip. Never a source of
    numbers."""
    events = [e for p in trace["planes"] if p["name"] == HOST_PLANE
              for line in p["lines"] if "XLAPjRtCpuClient" in line["name"]
              for e in line["events"] if e[2] > 0]
    plane = {"name": "/device:TPU:0",
             "lines": [{"name": OPS_LINE, "events": events}]}
    return {"planes": trace["planes"] + [plane]}


def host_events(trace: dict, prefix: str = "") -> list:
    """[name, start_ns, dur_ns] of every host-thread event whose name
    starts with ``prefix``."""
    return [e for p in trace["planes"] if p["name"] == HOST_PLANE
            for line in p["lines"] for e in line["events"]
            if e[0].startswith(prefix)]


def window_of(trace: dict, begin: str, end: str):
    """[lo, hi) in trace time between the starts of two host
    annotations the harness emitted, or the span of all device events
    if they are missing."""
    marks = {e[0]: e[1] for e in host_events(trace, "bench.")}
    if begin in marks and end in marks:
        return marks[begin], marks[end]
    ev = [e for p in device_planes(trace) for e in _line(p, OPS_LINE)]
    if not ev:
        raise ValueError("the trace holds no device operation")
    return min(e[1] for e in ev), max(e[1] + e[2] for e in ev)


def reduce(trace: dict, lo: float, hi: float, host_spans=()) -> dict:
    """Everything the readers need, over the window [lo, hi) ns.

    ``host_spans`` are (name, start_ns, end_ns) on the trace's clock:
    the program's own spans and the harness's. A gap in which no
    operation ran on device 0 is attributed to the innermost span over
    it, else ``unattributed``.
    """
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    window = hi - lo
    busy, exposed, ops, counts, programs = [], [], {}, {}, {}
    gaps0 = []
    for i, plane in enumerate(planes):
        events = [e for e in _line(plane, OPS_LINE)
                  if e[1] < hi and e[1] + e[2] > lo]
        covered = clip(union([e[1], e[1] + e[2]] for e in events), lo, hi)
        busy.append(total(covered))
        leaves = self_times(events)
        coll = union([s, e] for n, s, e, _ in leaves if COLLECTIVE.search(n))
        work = union([s, e] for n, s, e, own in leaves
                     if own > 0 and not COLLECTIVE.search(n)
                     and not n.startswith("while"))
        exposed.append(total(clip(subtract(coll, work), lo, hi)))
        if i == 0:
            for name, _, _, own in leaves:
                ops[name] = ops.get(name, 0.0) + own
                counts[name] = counts.get(name, 0) + 1
            gaps0 = subtract([[lo, hi]], covered)
            for name, start, dur in _line(plane, MODULES_LINE):
                if lo <= start < hi:
                    programs.setdefault(_program(name), []).append(dur)
    if not any(busy):
        raise ValueError("no operation ran on the device in the window")

    def owner(gap):
        """The innermost span over the gap: of those that cover at
        least half of it the shortest, else the one that covers most."""
        length = gap[1] - gap[0]
        over = [(min(e, gap[1]) - max(s, gap[0]), e - s, name)
                for name, s, e in host_spans if e > gap[0] and s < gap[1]]
        if not over:
            return "unattributed"
        half = [o for o in over if o[0] >= 0.5 * length]
        return (min(half, key=lambda o: o[1]) if half
                else max(over, key=lambda o: o[0]))[2]

    idle = {}
    for gap in gaps0:
        name = owner(gap)
        idle[name] = idle.get(name, 0.0) + (gap[1] - gap[0])
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": window / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "busy_s_per_device": [b / 1e9 for b in busy],
        "collective_exposed_s": sum(exposed) / len(exposed) / 1e9,
        "op_self_s": {k: v / 1e9 for k, v in ops.items()},
        "op_count": counts,
        "program_s": {k: [d / 1e9 for d in v] for k, v in programs.items()},
        "longest_gaps_s": sorted(((g[1] - g[0]) / 1e9 for g in gaps0),
                                 reverse=True)[:10],
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(idle)},
    }


def _program(module_event_name: str) -> str:
    """``jit_decode_chunk(1234567)`` -> ``decode_chunk``."""
    name = module_event_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


# ------------------------------------------------------------- by hand

def summary(trace: dict, top: int = 25) -> str:
    out = []
    for plane in trace["planes"]:
        out.append(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            ev = line["events"]
            if not ev:
                continue
            span = (max(e[1] + e[2] for e in ev) - min(e[1] for e in ev)) / 1e6
            out.append(f"  LINE {line['name']!r}: {len(ev)} events over "
                       f"{span:.1f} ms")
            agg = {}
            for name, own in ((n, o) for n, _, _, o in self_times(ev)):
                n, t = agg.get(name, (0, 0.0))
                agg[name] = (n + 1, t + own)
            rows = sorted(agg.items(), key=lambda kv: -kv[1][1])[:top]
            out += [f"    {t / 1e6:10.3f} ms self  x{n:<6d} {name[:110]}"
                    for name, (n, t) in rows]
    return "\n".join(out)


def cut(trace: dict, lo: float, hi: float) -> dict:
    """The events that begin in [lo, hi) ns: a small piece of a real
    trace, to keep in the repo for the tests."""
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": line["name"],
             "events": [e for e in line["events"] if lo <= e[1] < hi]}
            for line in p["lines"]]} for p in trace["planes"]]}


def main(argv) -> int:
    """trace_reduce <trace> [<out.json[.gz]> [<from ms> <to ms>]]"""
    trace = load_xplane(argv[1])
    print(summary(trace))
    if len(argv) > 2:
        if len(argv) > 4:
            trace = cut(trace, float(argv[3]) * 1e6, float(argv[4]) * 1e6)
        import gzip

        opener = gzip.open if argv[2].endswith(".gz") else open
        with opener(argv[2], "wt") as f:
            json.dump(trace, f, separators=(",", ":"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
