"""From a profiler trace to per-device intervals, and from those to the
numbers the per-layer metrics read.

The JAX profiler writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`.
Device planes are named `/device:TPU:<i>`; each operation the device ran
is an event of the plane's `XLA Ops` line, with a start and a duration in
nanoseconds. The host's own spans (`bench/input`, `bench/step`,
`bench/loss_read`, written with `jax.profiler.TraceAnnotation`) are
events of a host plane's lines.

Collectives are recognised by the HLO instruction name (`all-gather`, `all-reduce`,
`reduce-scatter`, `collective-permute`, `all-to-all`, each possibly with
`-start` / `-done`); an asynchronous one counts from its start's begin
to its done's end, a start paired with the next done of its kind.
"""
from __future__ import annotations

import re
import shutil
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?(\.\d+)?$")
# an event's name is its HLO text, `%<name> = <type> <op>(...)`
HLO = re.compile(r"^%?([\w.\-]+)(?: = (\(?[a-z0-9]+\[[0-9,]*\]))?")
# ops that only contain others (a scan's loop): their time is their body's
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
HOST_SPANS = ("bench/input", "bench/step", "bench/loss_read")


def clear(d: Path) -> None:
    if d.exists():
        shutil.rmtree(d)
    d.mkdir(parents=True)


def union(intervals) -> list[tuple[float, float]]:
    """Merged (start, end) intervals, sorted."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(intervals, cover) -> float:
    """Length of `intervals` (merged) not covered by `cover`."""
    cov = union(cover)
    total = 0.0
    for s, e in union(intervals):
        t = s
        for cs, ce in cov:
            if ce <= t or cs >= e:
                continue
            if cs > t:
                total += cs - t
            t = max(t, ce)
            if t >= e:
                break
        if t < e:
            total += e - t
    return total


def instruction(name: str) -> str:
    return HLO.match(name).group(1)


def short(name: str) -> str:
    """`%fusion.2 = bf16[12544,5120]{...} fusion(...)` -> `fusion.2 bf16[12544,5120]`."""
    m = HLO.match(name)
    return " ".join(g for g in m.groups() if g)


def collective_intervals(ops) -> list[tuple[float, float]]:
    """ops: (name, start, end). Synchronous collectives as they are;
    a `-start` paired with the next `-done` of its kind."""
    out = []
    open_: dict[str, list[float]] = {}
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        m = COLLECTIVE.match(instruction(name))
        if not m:
            continue
        kind, phase = m.group(1), m.group(2)
        if phase == "-start":
            open_.setdefault(kind, []).append(s)
        elif phase == "-done":
            starts = open_.get(kind)
            out.append((starts.pop(0) if starts else s, e))
        else:
            out.append((s, e))
    for starts in open_.values():     # a start with no done in the trace
        out.extend((s, s) for s in starts)
    return out


def device_summary(ops) -> dict:
    """Busy time, per-op totals, collective and exposed collective time
    of one device. ops: (name, start_s, end_s)."""
    busy = union((s, e) for _, s, e in ops)
    coll = collective_intervals(ops)
    compute = [(s, e) for n, s, e in ops
               if not COLLECTIVE.match(instruction(n))
               and not CONTAINER.match(instruction(n))]
    per_op: dict[str, float] = {}
    for n, s, e in ops:
        if not CONTAINER.match(instruction(n)):
            per_op[short(n)] = per_op.get(short(n), 0.0) + (e - s)
    return {"busy_s": length(busy), "busy": busy,
            "collective_s": length(coll),
            "exposed_collective_s": subtract(coll, compute),
            "per_op": per_op}


def idle_gaps(busy, window, spans) -> dict[str, float]:
    """Seconds of device idle inside `window`, by the host span (name)
    that covers the middle of each gap; `other` where none does."""
    lo, hi = window
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    out: dict[str, float] = {}
    for s, e in gaps:
        if e <= s:
            continue
        mid = (s + e) / 2
        name = next((n for n, a, b in spans if a <= mid <= b), "other")
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def load(d: Path):
    """(per-device ops, host spans) of the newest trace under d, times in
    seconds on the trace's clock."""
    from jax.profiler import ProfileData
    files = sorted(Path(d).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        return {}, []
    pd = ProfileData.from_file(str(files[-1]))
    devices: dict[int, list] = {}
    spans = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    (ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    for ev in line.events)
            elif not m and plane.name.startswith("/host"):
                spans.extend((ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                             for ev in line.events if ev.name in HOST_SPANS)
    return devices, spans


def reduce(d: Path) -> dict:
    """What the per-layer readers need of a trace: per-device summaries,
    the traced window (first host span's start to the last one's end),
    top device ops and idle gaps of the busiest device."""
    devices, spans = load(d)
    if not devices:
        return {}
    summ = {i: device_summary(ops) for i, ops in devices.items()}
    if spans:
        window = (min(s for _, s, _ in spans), max(e for _, _, e in spans))
    else:
        allb = [iv for s in summ.values() for iv in s["busy"]]
        window = (min(s for s, _ in allb), max(e for _, e in allb))
    busiest = max(summ, key=lambda i: summ[i]["busy_s"])
    b = summ[busiest]
    top = sorted(b["per_op"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle_gaps(b["busy"], window, spans).items(),
                  key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window[1] - window[0],
        "busy_s": sum(s["busy_s"] for s in summ.values()) / len(summ),
        "busiest": busiest,
        "devices": {i: {k: v for k, v in s.items() if k != "busy"}
                    for i, s in summ.items()},
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [[k, v] for k, v in gaps],
    }
