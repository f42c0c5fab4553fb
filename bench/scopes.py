"""From a profiler trace to device time under the program's scope names,
and to the device's idle time inside the program's own host spans.

A TPU trace names each device op (an event of the `XLA Ops` line) by its
HLO instruction alone. The compiled module of every program that ran is
in the same file: the `/host:metadata` plane holds, per program, a
serialized `xla.HloProto` under the name its `XLA Modules` events carry
(`jit_step(<id>)`), and each instruction's `metadata.op_name` there is
the path of `jax.named_scope`s it was staged under
(`jit(step)/transpose(jvp(model/layers))/while/body/.../model/attn/dot_general`).
An op is put in the program whose module event holds its start, joined on
its instruction name, and then under the innermost scope of its path that
the program names (`SCOPE`). An op whose own path holds no such scope (the
compiler made it without a source) takes the path of the innermost
container it runs in (a scan's `while`); one that still has none, or that
cannot be joined, is unscoped. Containers are not counted themselves, as
their time is their body's.

Layer scopes (`model/*`, `zero3/*`, `optim/*`) partition the step; the
schedule executor's scopes (`exec/*`, `bucket/*`, `sync/*`, `overlap/*`)
nest inside them, and an op under an executor scope outside every layer
scope counts to the outermost executor scope. Each op's phase is read
from its path: recompute under `rematted_computation`, backward under
`transpose(`, forward otherwise.

A configuration file may list `scopes`: names its program opens inside a
layer scope (`model/mlp/experts`). They are matched before the fixed
names, the longest first, and an op under one goes to that name in
`by_scope`; its layer, and so `by_layer`, `unscoped_s` and `op_s`, is the
same whether the names are listed or not. A listed name outside every
layer scope is not a scope.

The program's host spans (`data/*`, and the prefixes a configuration
file lists under `program_spans`, written by `runtime/trace.py` as
profiler annotations) are loaded beside the bench's own.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path

import harness
import tracing

FIXED = (r"model/(?:embed|layers|attn|mlp|head)"
         r"|zero3/(?:gather|scatter)|optim/(?:clip|adamw)"
         r"|(?:exec|bucket|sync|overlap)/[a-z0-9_]+(?:/step\d+)?")
SCOPE = re.compile(rf"(?<![\w.])({FIXED})(?!\w)")
LAYER = ("model/", "zero3/", "optim/")
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
PROGRAM_SPANS = ("data/",)


# ---------------------------------------------------------------------------
# the compiled modules a trace carries
# ---------------------------------------------------------------------------
def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b):
    """(field number, value) of one protobuf message, in order; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _field(b, number: int):
    return next((v for f, v in _fields(b) if f == number), None)


def _text(b) -> str:
    return bytes(b).decode() if b is not None else ""


def _instructions(hlo_proto) -> dict[str, str]:
    """{instruction name: op_name} of an `xla.HloProto`: its module (1),
    computations (3), instructions (2), each with name (1) and metadata
    (7), whose op_name is field 2."""
    out = {}
    for f, comp in _fields(_field(hlo_proto, 1) or b""):
        if f != 3:
            continue
        for g, ins in _fields(comp):
            if g != 2:
                continue
            name = meta = None
            for h, v in _fields(ins):
                if h == 1:
                    name = v
                elif h == 7:
                    meta = v
            if name is not None:
                out[_text(name)] = _text(_field(meta, 2)) if meta else ""
    return out


def op_names(xspace) -> dict[str, dict[str, str]]:
    """{module name: {instruction: op_name}} from the metadata plane of a
    serialized `XSpace` (planes 1; plane name 2, event metadata 4, stat
    metadata 5; event metadata name 2, stats 5; stat metadata id 1 and
    bytes value 6)."""
    b = memoryview(xspace)
    out = {}
    for f, plane in _fields(b):
        if f != 1 or _text(_field(plane, 2)) != METADATA_PLANE:
            continue
        stat_ids = set()
        for g, entry in _fields(plane):
            if g == 5:
                key, meta = _field(entry, 1), _field(entry, 2)
                if _text(_field(meta, 2)) == HLO_STAT:
                    stat_ids.add(key)
        for g, entry in _fields(plane):
            if g != 4:
                continue
            meta = _field(entry, 2)
            for h, stat in _fields(meta):
                if h == 5 and _field(stat, 1) in stat_ids:
                    proto = _field(stat, 6)
                    if proto is not None:
                        out[_text(_field(meta, 2))] = _instructions(proto)
    return out


# ---------------------------------------------------------------------------
# ops under scopes
# ---------------------------------------------------------------------------
def scope_pattern(listed=()) -> re.Pattern:
    """SCOPE with a configuration file's own scope names before the fixed
    ones, the longest first."""
    if not listed:
        return SCOPE
    names = "|".join(re.escape(n) for n in sorted(set(listed), key=len,
                                                  reverse=True))
    return re.compile(rf"(?<![\w.])({names}|{FIXED})(?!\w)")


def scopes_of(op_name: str, pattern: re.Pattern = SCOPE
              ) -> tuple[str | None, str | None]:
    """(innermost scope, layer) of an op's path; (None, None) unscoped.
    The layer is read by the fixed names alone; `pattern` may add names
    nested in it (`scope_pattern`)."""
    found = SCOPE.findall(op_name)
    if not found:
        return None, None
    layers = [s for s in found if s.startswith(LAYER)]
    return pattern.findall(op_name)[-1], (layers[-1] if layers else found[0])


def phase(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "remat"
    return "bwd" if "transpose(" in op_name else "fwd"


def split(ops, modules, names, listed=()) -> dict:
    """Seconds of the device's ops by innermost scope and phase, by layer,
    and unscoped. ops: (HLO text, start, end); modules: (module name,
    start, end) of the `XLA Modules` line; names: `op_names`'s map;
    listed: a configuration file's own scope names."""
    pattern = scope_pattern(listed)
    modules = sorted(modules, key=lambda m: m[1])
    starts = [s for _, s, _ in modules]

    def path(ins: str, s: float) -> str:
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or s > modules[k][2]:
            return ""
        return names.get(modules[k][0], {}).get(ins, "")

    containers = [(path(tracing.instruction(t), s), s, e) for t, s, e in ops
                  if tracing.CONTAINER.match(tracing.instruction(t))]
    by_scope: dict[str, dict[str, float]] = {}
    by_layer: dict[str, float] = {}
    unscoped = total = 0.0
    for text, s, e in ops:
        ins = tracing.instruction(text)
        if tracing.CONTAINER.match(ins):
            continue
        total += e - s
        op_name = path(ins, s)
        if not SCOPE.search(op_name):
            # an op the compiler made without a source runs where its
            # innermost container (a scan's loop) runs
            inside = [c for c in containers if c[1] <= s < c[2]]
            if inside:
                op_name = max(inside, key=lambda c: c[1])[0]
        scope, layer = scopes_of(op_name, pattern)
        if scope is None:
            unscoped += e - s
            continue
        ph = by_scope.setdefault(scope, {"fwd": 0.0, "bwd": 0.0,
                                         "remat": 0.0})
        ph[phase(op_name)] += e - s
        by_layer[layer] = by_layer.get(layer, 0.0) + (e - s)
    return {"by_scope": by_scope, "by_layer": by_layer,
            "unscoped_s": unscoped, "op_s": total}


def input_wait(busy, spans) -> float:
    """Seconds of the program's `data/*` spans in which the device ran
    nothing. busy: merged op intervals; spans: (name, start, end)."""
    return tracing.subtract([(s, e) for n, s, e in spans
                             if n.startswith(PROGRAM_SPANS)], busy)


# ---------------------------------------------------------------------------
# one traced run
# ---------------------------------------------------------------------------
def load(path: Path, span_prefixes=PROGRAM_SPANS):
    """(per-device ops, per-device module events, host spans with the
    program's own that start with one of `span_prefixes`, module op_names)
    of one `.xplane.pb`, in seconds."""
    from jax.profiler import ProfileData
    raw = path.read_bytes()
    pd = ProfileData.from_serialized_xspace(raw)
    ops: dict[int, list] = {}
    mods: dict[int, list] = {}
    spans = []
    for plane in pd.planes:
        m = tracing.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (tracing.OPS_LINE, MODULES_LINE):
                into = ops if line.name == tracing.OPS_LINE else mods
                into.setdefault(int(m.group(1)), []).extend(
                    (ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    for ev in line.events)
            elif not m and plane.name.startswith("/host"):
                spans.extend(
                    (ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    for ev in line.events
                    if ev.name in tracing.HOST_SPANS
                    or ev.name.startswith(span_prefixes))
    return ops, mods, spans, op_names(raw)


def reduce(path: Path, listed=(), span_prefixes=()) -> dict:
    """The busiest device's scope split (with a configuration file's own
    scope names, `listed`), the idle time inside the program's `data/*`
    spans, and the seconds of each program span loaded (`data/*` and
    `span_prefixes`), with the window as `tracing.reduce` takes it."""
    prefixes = PROGRAM_SPANS + tuple(span_prefixes)
    ops, mods, spans, names = load(path, prefixes)
    if not ops:
        return {}
    busy = {i: tracing.union((s, e) for _, s, e in o) for i, o in ops.items()}
    busiest = max(busy, key=lambda i: tracing.length(busy[i]))
    bench = [sp for sp in spans if sp[0] in tracing.HOST_SPANS]
    if bench:
        window = (min(s for _, s, _ in bench), max(e for _, _, e in bench))
    else:
        window = (busy[busiest][0][0], busy[busiest][-1][1])
    out = split(ops[busiest], mods.get(busiest, []), names, listed)
    span_s: dict[str, float] = {}
    for n, s, e in spans:
        if n.startswith(prefixes):
            span_s[n] = span_s.get(n, 0.0) + (e - s)
    begins = sorted(s for n, s, _ in bench if n == "bench/input")
    ends = sorted(e for n, _, e in bench if n == "bench/loss_read")
    out.update(step_s=[e - s for s, e in zip(begins, ends)],
               window_s=window[1] - window[0],
               busy_s=tracing.length(busy[busiest]),
               input_wait_s=input_wait(busy[busiest], spans),
               program_spans=sum(1 for n, _, _ in spans
                                 if n.startswith(PROGRAM_SPANS)),
               span_s=span_s)
    return out


def log_split(r: dict, steps: int) -> None:
    ms = 1e3 / steps
    for scope, ph in sorted(r["by_scope"].items(),
                            key=lambda kv: -sum(kv[1].values())):
        harness.log(f"scope {scope}: {ms * sum(ph.values()):.3f} ms/step "
                    f"(fwd {ms * ph['fwd']:.3f}, bwd {ms * ph['bwd']:.3f}, "
                    f"remat {ms * ph['remat']:.3f})")
    scoped = sum(r["by_layer"].values())
    harness.log(
        f"scopes: layers {ms * scoped:.3f} + unscoped "
        f"{ms * r['unscoped_s']:.3f} = {ms * r['op_s']:.3f} ms/step of ops; "
        f"device busy {ms * r['busy_s']:.3f} ms/step "
        f"({100 * (r['op_s'] / max(r['busy_s'], 1e-12) - 1):+.3f}%); "
        f"input wait {ms * r['input_wait_s']:.3f} ms/step in "
        f"{r['program_spans']} program spans")
    if r["step_s"]:
        med = sorted(r["step_s"])[len(r["step_s"]) // 2]
        harness.log(f"traced steps: median {1e3 * med:.1f} ms over "
                    f"{len(r['step_s'])} (bench/input to bench/loss_read)")


def of_run(run: dict) -> dict:
    """The split of the run's trace (the newest under the bench's trace
    directory, taken only if its window is the one the run's own reduction
    read), by the scope names and program span prefixes its configuration
    file lists (the record's `config`), worked out once per run and kept in
    the run's record."""
    if "scopes" in run:
        return run["scopes"]
    run["scopes"] = {}
    tr = run.get("trace") or {}
    if not tr.get("window_s") or not run.get("trace_steps"):
        return run["scopes"]
    files = sorted(Path(harness.TRACE_DIR).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return run["scopes"]
    conf = run.get("config") or {}
    r = reduce(files[-1], conf.get("scopes", ()),
               conf.get("program_spans", ()))
    if r and abs(r["window_s"] - tr["window_s"]) <= 1e-9 * tr["window_s"]:
        log_split(r, run["trace_steps"])
        run["scopes"] = r
    return run["scopes"]


def ms_per_step(run: dict, prefixes: tuple[str, ...]):
    """Device ms per traced step under the layers that start with one of
    `prefixes`; None when no op of the trace is under one."""
    r = of_run(run)
    got = [v for k, v in r.get("by_layer", {}).items()
           if k.startswith(prefixes)]
    return 1e3 * sum(got) / run["trace_steps"] if got else None


def ms_per_scope(run: dict, names) -> float | None:
    """Device ms per traced step under the scopes `names` (fixed ones, or
    those a configuration file lists), all phases summed; an op under a
    name nested in one counts to the nested name alone. None when no op
    of the trace is under one."""
    r = of_run(run)
    got = [sum(ph.values()) for k, ph in r.get("by_scope", {}).items()
           if k in names]
    return 1e3 * sum(got) / run["trace_steps"] if got else None
