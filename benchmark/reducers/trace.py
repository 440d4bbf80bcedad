"""From a profiler trace to device busy time, per-operation time and idle gaps
named by what the host was doing.

``read_events`` turns an ``.xplane.pb`` into plain tuples; everything after
works on those, so that the arithmetic is tested on a small recorded trace
(``tests/data/trace_small.json``) with no profiler and no chip.

An event is ``(plane, line, name, start_ns, dur_ns, detail)``. Device planes
are those named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
for every operation that ran on the chip. Host threads are the lines of the
``/host:CPU`` plane; the benchmark's ``TraceAnnotation``s (``bench.*``) and
jax's own host events (``PjitFunction(...)``, ``shard_args``...) lie there.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Sequence, Tuple

Event = Tuple[str, str, str, int, int, str]
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SHORT_GAP_NS = 1_333_000     # gaps under this are pooled into one entry
WINDOW_OPEN, WINDOW_CLOSE = "bench.window_open", "bench.window_close"


def read_events(xplane_path: str) -> List[Event]:
    from jax.profiler import ProfileData

    out: List[Event] = []
    data = ProfileData.from_file(xplane_path)
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                name, detail = ev.name, ""
                if device:      # the TPU's events are named by their HLO text
                    name, detail = ev.name.split(" = ")[0], ev.name
                out.append((plane.name, line.name, name, int(ev.start_ns),
                            int(ev.duration_ns), detail))
    return out


def op_label(name: str, detail: str) -> str:
    """A name that survives renumbering: the operation's kind and the shapes
    of its result, taken from the HLO text where the trace carries it
    (``%fusion.12 = (f32[8,12,512,512]{...}, ...) fusion(...), kind=kOutput``)."""
    base = re.sub(r"[.\d]+$", "", name.lstrip("%"))
    m = re.search(r"=\s*(\(.*?\)|\S+)\s+[\w-]+\(", detail)
    shapes = re.findall(r"\b([a-z]+\d*\[[\d,]*\])", m.group(1)) if m else []
    kind = re.search(r"kind=(\w+)", detail)
    label = base + ("_" + kind.group(1) if kind else "")
    if shapes:
        label += " " + " ".join(shapes[:3])
    return label


def op_operands(detail: str) -> str:
    """The shapes an operation reads, from the same HLO text."""
    m = re.search(r"=\s*(?:\(.*?\)|\S+)\s+[\w-]+\((.*)\)", detail)
    return " ".join(re.findall(r"\b([a-z]+\d*\[[\d,]*\])", m.group(1))) if m else ""


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def reduce_events(events: Sequence[Event]) -> Dict[str, Any]:
    """Busy seconds (union of device-operation intervals, averaged over the
    chips), the traced window (from the ``bench.window_open`` marker to the
    ``bench.window_close`` marker; first to last event of any kind where a
    trace has no markers), seconds per operation label, and the idle gaps of
    the first chip attributed to the innermost benchmark annotation and host
    event that cover each gap's start (short gaps are pooled by annotation). Events are clipped to the window."""
    opens = [e[3] + e[4] for e in events if e[2] == WINDOW_OPEN]
    closes = [e[3] for e in events if e[2] == WINDOW_CLOSE]
    t0 = min(opens) if opens else min(e[3] for e in events)
    t1 = max(closes) if closes else max(e[3] + e[4] for e in events)
    events = [(p, l, n, max(s, t0), min(s + d, t1) - max(s, t0), x)
              for p, l, n, s, d, x in events
              if s < t1 and s + d > t0 and n not in (WINDOW_OPEN, WINDOW_CLOSE)]
    dev = [e for e in events if DEVICE_PLANE.match(e[0])]
    host = [e for e in events if not DEVICE_PLANE.match(e[0])]
    if not dev:
        return {"busy_s": 0.0, "window_s": (t1 - t0) / 1e9, "device_ops": [],
                "idle_gaps": [], "ops_all": [],
                "summary": "no operation ran on a device"}
    planes = sorted({e[0] for e in dev})
    busy, per_op = [], {}
    for p in planes:
        iv = _union([(e[3], e[3] + e[4]) for e in dev if e[0] == p])
        busy.append(sum(e - s for s, e in iv))
    for _, _, name, _, dur, detail in dev:
        lab = op_label(name, detail)
        tot, cnt, reads = per_op.get(lab, (0, 0, op_operands(detail)))
        per_op[lab] = (tot + dur, cnt + 1, reads)
    n = len(planes)
    ops_all = sorted(((lab, tot / 1e9 / n, cnt // n or 1, reads)
                      for lab, (tot, cnt, reads) in per_op.items()),
                     key=lambda x: -x[1])

    first = _union([(e[3], e[3] + e[4]) for e in dev if e[0] == planes[0]])
    edges = [(t0, t0)] + first + [(t1, t1)]
    gaps = [(a[1], b[0]) for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    bench = sorted((e for e in host if e[2].startswith("bench.")), key=lambda e: e[3])
    other = sorted((e for e in host if not e[2].startswith("bench.")
                    and e[4] > 0), key=lambda e: e[3])

    def covering(evs, t):
        best = None
        for e in evs:
            if e[3] > t:
                break
            if e[3] + e[4] > t and (best is None or e[4] < best[4]):
                best = e
        return best

    named: Dict[str, float] = {}
    short: Dict[str, List[float]] = {}      # annotation -> [count, seconds]
    for s, e in gaps:
        a = covering(bench, s)
        if e - s < SHORT_GAP_NS:
            pool = short.setdefault(a[2] if a else "no_annotation", [0, 0.0])
            pool[0], pool[1] = pool[0] + 1, pool[1] + (e - s) / 1e9
            continue
        h = covering(other, s)
        label = (f"{a[2] if a else 'no_annotation'} / "
                 f"{re.sub(r'[^A-Za-z0-9_.:()-]+', '_', h[2])[:48] if h else 'no_host_event'}")
        named[label] = named.get(label, 0.0) + (e - s) / 1e9
    for a, (count, seconds) in short.items():
        named[f"{a} / {count} gaps under {SHORT_GAP_NS / 1e6:.3f} ms"] = seconds
    idle = sorted(named.items(), key=lambda x: -x[1])
    busy_s, window_s = sum(busy) / n / 1e9, (t1 - t0) / 1e9
    return {"busy_s": busy_s, "window_s": window_s,
            "device_ops": [[f"{lab} x{cnt}", s] for lab, s, cnt, _ in ops_all[:10]],
            "idle_gaps": [[k, v] for k, v in idle[:10]],
            "ops_all": ops_all,
            "summary": f"{len(dev)} device events on {n} chip(s), busy "
                       f"{busy_s:.3f} s of {window_s:.3f} s, {len(gaps)} gaps"}


def reduce_dir(trace_dir: str) -> Dict[str, Any]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return reduce_events(read_events(paths[-1]))


def idle_pct(facts, mode: str):
    """1 - busy over the traced window, from the device trace alone."""
    tr = facts.get("trace")
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0 \
            or facts.get("mode") != mode:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
