"""The scope reduction (bench/scopes.py) on synthetic traces: the compiled
modules read from a trace's metadata plane, ops put under the innermost
scope, the sum against the device's busy time, the idle time inside the
program's spans, and the readers that return nothing without their
scope."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import scopes  # noqa: E402
import tracing  # noqa: E402


# ---------------------------------------------------------------------------
# a serialized XSpace, field by field
# ---------------------------------------------------------------------------
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(field: int, v: int) -> bytes:
    return _varint(field << 3) + _varint(v)


def _bytes(field: int, v) -> bytes:
    v = v.encode() if isinstance(v, str) else v
    return _varint(field << 3 | 2) + _varint(len(v)) + v


def _instruction(name: str, op_name: str | None) -> bytes:
    meta = _bytes(7, _bytes(1, "dot") + _bytes(2, op_name)) \
        if op_name is not None else b""
    return _bytes(1, name) + _bytes(2, "fusion") + meta


def _xspace(modules: dict[str, dict[str, str | None]]) -> bytes:
    hlo_stat = 7
    plane = _bytes(2, "/host:metadata") + _bytes(5, _int(1, hlo_stat) + _bytes(
        2, _int(1, hlo_stat) + _bytes(2, "Hlo Proto")))
    for i, (name, ins) in enumerate(modules.items()):
        comp = _bytes(1, "main") + b"".join(
            _bytes(2, _instruction(n, o)) for n, o in ins.items())
        hlo = _bytes(1, _bytes(1, name) + _bytes(3, comp))
        meta = _int(1, i + 1) + _bytes(2, name) + _bytes(
            5, _int(1, hlo_stat) + _bytes(6, hlo))
        plane += _bytes(4, _int(1, i + 1) + _bytes(2, meta))
    device = _bytes(2, "/device:TPU:0") + _bytes(3, _bytes(2, "XLA Ops"))
    return _bytes(1, device) + _bytes(1, plane) + _bytes(3, "a warning")


def test_op_names_read_from_the_metadata_plane():
    got = scopes.op_names(_xspace({
        "jit_step(11)": {"fusion.1": "jit(step)/model/attn/dot_general",
                         "copy.2": None},
        "jit_init(12)": {"fusion.1": "jit(init)/mul"}}))
    assert got == {"jit_step(11)": {"fusion.1": "jit(step)/model/attn/"
                                    "dot_general", "copy.2": ""},
                   "jit_init(12)": {"fusion.1": "jit(init)/mul"}}
    assert scopes.op_names(b"") == {}


# ---------------------------------------------------------------------------
# ops under scopes
# ---------------------------------------------------------------------------
LAYERS = "jit(step)/transpose(jvp(model/layers))/while"
NAMES = {"jit_step(1)": {
    "fusion.1": "jit(step)/jvp(model/layers)/while/body/closed_call/"
                "model/attn/dot_general",
    "fusion.2": LAYERS + "/body/closed_call/checkpoint/"
                "rematted_computation/model/mlp/dot_general",
    "fusion.3": LAYERS + "/body/dynamic_update_slice",
    "while.4": LAYERS,
    "fusion.5": "",
    "fusion.6": "jit(step)/zero3/scatter/bucket/zero3_rs0/"
                "exec/reduce_scatter/exec/rs/step0/exec/round1/ppermute",
    "copy.7": "",
    "fusion.8": "jit(step)/optim/adamw/sub;jit(step)/optim/adamw/mul",
    "fusion.9": "jit(step)/sync/gradients/exec/allreduce/exec/fold0/add",
    "fusion.10": "jit(step)/jvp(model/embed)/mul",
}}
OPS = [
    ("%fusion.10 = bf16[8,4]{1,0} fusion(%p.1)", 0.0, 0.5),
    ("%fusion.1 = bf16[8,4]{1,0} fusion(%p.1)", 0.5, 1.5),
    ("%while.4 = (s32[]) while(%tuple.1)", 2.0, 5.0),
    ("%fusion.2 = bf16[8,4]{1,0} fusion(%p.2)", 2.0, 3.0),
    ("%fusion.3 = bf16[8,4]{1,0} fusion(%p.3)", 3.0, 4.0),
    ("%fusion.5 = bf16[8,4]{1,0} fusion(%p.3)", 4.0, 5.0),    # in while.4
    ("%fusion.6 = f32[64]{0} fusion(%p.4)", 5.0, 5.5),
    ("%copy.7 = f32[64]{0} copy(%p.5)", 5.5, 6.0),            # no source
    ("%fusion.8 = f32[64]{0} fusion(%p.6)", 6.0, 7.0),
    ("%fusion.9 = f32[64]{0} fusion(%p.7)", 7.0, 7.25),
    ("%fusion.11 = f32[64]{0} fusion(%p.8)", 7.25, 7.5),      # not joined
    ("%fusion.1 = f32[64]{0} fusion(%p.9)", 11.0, 11.5),      # no module
]
MODULES = [("jit_step(1)", 0.0, 10.0)]


def test_innermost_scope_wins_and_names_the_layer():
    assert scopes.scopes_of(NAMES["jit_step(1)"]["fusion.6"]) == (
        "exec/round1", "zero3/scatter")
    assert scopes.scopes_of(NAMES["jit_step(1)"]["fusion.9"]) == (
        "exec/fold0", "sync/gradients")
    assert scopes.scopes_of(NAMES["jit_step(1)"]["fusion.2"]) == (
        "model/mlp", "model/mlp")
    assert scopes.scopes_of("jit(step)/model/attention/dot") == (None, None)
    assert scopes.scopes_of("") == (None, None)


def test_split_by_scope_phase_and_layer():
    r = scopes.split(OPS, MODULES, NAMES)
    assert r["by_scope"]["model/attn"] == {"fwd": 1.0, "bwd": 0.0,
                                           "remat": 0.0}
    assert r["by_scope"]["model/mlp"]["remat"] == 1.0
    # fusion.5 has no source and runs inside the backward scan's loop
    assert r["by_scope"]["model/layers"] == {"fwd": 0.0, "bwd": 2.0,
                                             "remat": 0.0}
    assert r["by_layer"] == pytest.approx({
        "model/embed": 0.5, "model/attn": 1.0, "model/mlp": 1.0,
        "model/layers": 2.0, "zero3/scatter": 0.5, "optim/adamw": 1.0,
        "sync/gradients": 0.25})
    # the container is not counted; the copy without a source, the op
    # that names no instruction of its module and the one outside every
    # module are unscoped
    assert r["unscoped_s"] == pytest.approx(0.5 + 0.25 + 0.5)
    assert r["op_s"] == pytest.approx(7.5)


def test_scoped_and_unscoped_add_up_to_the_busy_time():
    r = scopes.split(OPS, MODULES, NAMES)
    assert sum(r["by_layer"].values()) + r["unscoped_s"] == \
        pytest.approx(r["op_s"], abs=1e-12)
    busy = tracing.length((s, e) for _, s, e in OPS)
    assert abs(r["op_s"] / busy - 1) < 0.01


# names a configuration file lists, nested in the fixed layer scopes: one
# opened by its full name, one opened inside `model/mlp`, one that never
# runs, and one outside every layer scope
LISTED = ("model/mlp/experts", "model/mlp/router", "model/attn/latent",
          "model/mlp/shared", "moe/plan")
NESTED = {"jit_step(1)": dict(NAMES["jit_step(1)"], **{
    "fusion.12": LAYERS + "/body/closed_call/model/mlp/model/mlp/experts/"
                 "dot_general",
    "fusion.13": "jit(step)/transpose(jvp(model/layers))/model/mlp/router/"
                 "dot_general",
    "fusion.14": "jit(step)/jvp(model/layers)/model/attn/latent/dot",
    "fusion.15": "jit(step)/moe/plan/add",
    "fusion.16": "jit(step)/jvp(model/layers)/model/mlp/experts_up/dot",
})}
NESTED_OPS = OPS + [
    ("%fusion.12 = bf16[8,4]{1,0} fusion(%p.1)", 8.0, 8.5),
    ("%fusion.13 = bf16[8,4]{1,0} fusion(%p.1)", 8.5, 8.75),
    ("%fusion.14 = bf16[8,4]{1,0} fusion(%p.1)", 8.75, 9.0),
    ("%fusion.15 = bf16[8,4]{1,0} fusion(%p.1)", 9.0, 9.25),
    ("%fusion.16 = bf16[8,4]{1,0} fusion(%p.1)", 9.25, 9.5),
]


def test_listed_names_leave_the_layers_as_they_were():
    plain = scopes.split(NESTED_OPS, MODULES, NESTED)
    listed = scopes.split(NESTED_OPS, MODULES, NESTED, LISTED)
    for key in ("by_layer", "unscoped_s", "op_s"):
        assert listed[key] == plain[key], key
    assert plain["by_layer"]["model/mlp"] == pytest.approx(1.0 + 0.5 + 0.25
                                                           + 0.25)
    # the name outside every layer scope stays unscoped
    assert plain["unscoped_s"] == pytest.approx(1.25 + 0.25)


def test_listed_names_take_their_ops():
    r = scopes.split(NESTED_OPS, MODULES, NESTED, LISTED)
    assert r["by_scope"]["model/mlp/experts"] == {"fwd": 0.0, "bwd": 0.5,
                                                  "remat": 0.0}
    assert r["by_scope"]["model/mlp/router"]["bwd"] == 0.25
    assert r["by_scope"]["model/attn/latent"]["fwd"] == 0.25
    # what is left of the enclosing scopes: `experts_up` is no listed name
    assert sum(r["by_scope"]["model/mlp"].values()) == pytest.approx(1.25)
    assert sum(r["by_scope"]["model/attn"].values()) == pytest.approx(1.0)
    assert "model/mlp/shared" not in r["by_scope"]
    assert "moe/plan" not in r["by_scope"]
    plain = scopes.split(NESTED_OPS, MODULES, NESTED)
    assert not any(k in plain["by_scope"] for k in LISTED)
    assert sum(map(sum, (p.values() for p in r["by_scope"].values()))) == \
        pytest.approx(sum(r["by_layer"].values()))


def test_listed_names_match_the_longest_first():
    pattern = scopes.scope_pattern(("model/mlp", "model/mlp/experts"))
    assert scopes.scopes_of("a/model/mlp/experts/dot", pattern) == (
        "model/mlp/experts", "model/mlp")
    assert scopes.scope_pattern(()) is scopes.SCOPE


def test_ms_per_scope_reads_listed_names():
    r = scopes.split(NESTED_OPS, MODULES, NESTED, LISTED)
    run = {"trace_steps": 5, "scopes": r}
    assert scopes.ms_per_scope(run, ("model/mlp/experts",)) == \
        pytest.approx(1e3 * 0.5 / 5)
    assert scopes.ms_per_scope(run, ("model/mlp/experts",
                                     "model/mlp/router")) == \
        pytest.approx(1e3 * 0.75 / 5)
    # a fixed name reads its own ops, not the listed names nested in it
    assert scopes.ms_per_scope(run, ("model/mlp",)) == \
        pytest.approx(1e3 * 1.25 / 5)


@pytest.mark.parametrize("names", [("model/mlp/shared",), ("moe/plan",),
                                   ("model/mlp/expert",)])
def test_ms_per_scope_is_none_for_a_name_that_never_ran(names):
    r = scopes.split(NESTED_OPS, MODULES, NESTED, LISTED)
    assert scopes.ms_per_scope({"trace_steps": 5, "scopes": r}, names) is None
    assert scopes.ms_per_scope({"trace_steps": 5}, names) is None


def _plane(id_: int, name: str, lines: dict[str, list]) -> bytes:
    """An XSpace holding one plane whose lines hold events (name, start s,
    end s)."""
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = b"".join(_bytes(4, _int(1, ids[n]) + _bytes(
        2, _int(1, ids[n]) + _bytes(2, n))) for n in names)
    body = b""
    for k, (line, evs) in enumerate(lines.items()):
        events = b"".join(_bytes(4, _int(1, ids[n]) + _int(2, round(s * 1e12))
                                 + _int(3, round((e - s) * 1e12)))
                          for n, s, e in evs)
        body += _bytes(3, _int(1, k + 1) + _bytes(2, line) + _int(3, 0)
                       + events)
    return _bytes(1, _int(1, id_) + _bytes(2, name) + body + meta)


SPANS = [("bench/input", 0.0, 1.0), ("data/batch", 0.0, 0.5),
         ("moe/plan", 0.5, 0.75), ("moe/planner", 0.75, 0.8),
         ("train/step", 0.0, 1.0)]


def test_listed_program_spans_are_loaded(tmp_path):
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(_plane(2, "/host:CPU", {"python": SPANS}))
    _, _, spans, _ = scopes.load(f)
    assert sorted(n for n, _, _ in spans) == ["bench/input", "data/batch"]
    _, _, spans, _ = scopes.load(f, scopes.PROGRAM_SPANS + ("moe/plan",))
    assert sorted(n for n, _, _ in spans) == [
        "bench/input", "data/batch", "moe/plan", "moe/planner"]
    got = {n: e - s for n, s, e in spans}
    assert got["moe/plan"] == pytest.approx(0.25)


def test_a_trace_reduces_alike_with_and_without_listed_names(tmp_path):
    """A whole trace file (device ops and modules, the metadata plane, host
    spans): the layers, the busy time and the idle time inside `data/*`
    read the same with the names listed; the listed ones add their own
    scopes and spans."""
    ops = [(t, s + 100, e + 100) for t, s, e in NESTED_OPS]
    spans = [(n, s + 100, e + 100) for n, s, e in
             [("bench/input", 0.0, 0.5), ("data/batch", 0.0, 0.5),
              ("moe/plan", 8.0, 8.5), ("bench/loss_read", 9.0, 9.5)]]
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(_xspace(NESTED) + _plane(3, "/device:TPU:0", {
        "XLA Ops": ops, "XLA Modules": [("jit_step(1)", 100.0, 110.0)]})
        + _plane(4, "/host:CPU", {"python": spans}))
    plain = scopes.reduce(f)
    listed = scopes.reduce(f, LISTED, ("moe/",))
    assert plain["by_layer"] == pytest.approx(scopes.split(
        NESTED_OPS, MODULES, NESTED)["by_layer"])
    for key in ("by_layer", "unscoped_s", "op_s", "busy_s", "window_s",
                "input_wait_s", "program_spans", "step_s"):
        assert listed[key] == plain[key], key
    assert plain["program_spans"] == 1
    assert plain["span_s"] == pytest.approx({"data/batch": 0.5})
    assert listed["span_s"] == pytest.approx({"data/batch": 0.5,
                                              "moe/plan": 0.5})
    assert listed["by_scope"]["model/mlp/experts"]["bwd"] == \
        pytest.approx(0.5)
    assert not any(k in plain["by_scope"] for k in LISTED)


def test_the_record_names_what_the_reduction_reads(tmp_path, monkeypatch):
    """`of_run` takes the scope names and span prefixes from the record's
    configuration file, and reads the trace once."""
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    calls = []

    def reduce(path, listed=(), span_prefixes=()):
        calls.append((path.name, tuple(listed), tuple(span_prefixes)))
        return {"window_s": 2.0, "by_scope": {}, "by_layer": {},
                "unscoped_s": 0.0, "op_s": 0.0, "busy_s": 0.0,
                "input_wait_s": 0.0, "program_spans": 0, "step_s": []}
    monkeypatch.setattr(scopes, "reduce", reduce)
    run = {"trace": {"window_s": 2.0}, "trace_steps": 5,
           "config": {"scopes": ["model/mlp/experts"],
                      "program_spans": ["moe/"]}}
    assert scopes.of_run(run)["window_s"] == 2.0
    scopes.of_run(run)
    assert calls == [("t.xplane.pb", ("model/mlp/experts",), ("moe/",))]
    del run["scopes"], run["config"]
    scopes.of_run(run)
    assert calls[-1] == ("t.xplane.pb", (), ())


def test_input_wait_is_idle_inside_the_program_spans():
    busy = tracing.union([(0.0, 1.0), (2.0, 3.0)])
    spans = [("data/batch", 0.5, 2.5), ("bench/input", 0.0, 3.0),
             ("data/batch", 4.0, 5.0)]
    assert scopes.input_wait(busy, spans) == pytest.approx(2.0)
    assert scopes.input_wait(busy, spans[1:2]) == 0.0


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
READERS = {
    "attention_ms_per_step": ("model/attn",),
    "mlp_ms_per_step": ("model/mlp",),
    "embed_head_ms_per_step": ("model/embed", "model/head"),
    "optimizer_ms_per_step": ("optim/clip", "optim/adamw"),
    "zero3_sync_ms_per_step": ("zero3/gather", "zero3/scatter",
                               "sync/gradients"),
    "input_wait_ms_per_step": (),
    "unscoped_device_share": (),
}
ALL = {"model/embed": 0.01, "model/head": 0.02, "model/attn": 0.5,
       "model/mlp": 0.4, "model/layers": 0.03, "optim/clip": 0.05,
       "optim/adamw": 0.2, "zero3/gather": 0.04, "zero3/scatter": 0.06,
       "sync/gradients": 0.01}


def _run(by_layer: dict, program_spans: int = 5) -> dict:
    total = sum(by_layer.values()) + 0.1
    return {"trace_steps": 5, "scopes": {
        "by_layer": dict(by_layer), "unscoped_s": 0.1, "op_s": total,
        "input_wait_s": 0.12, "program_spans": program_spans}}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_its_scopes(metric):
    got = harness.metric_reader(metric)(_run(ALL))
    want = {"input_wait_ms_per_step": 24.0,
            "unscoped_device_share": 100 * 0.1 / (sum(ALL.values()) + 0.1)}
    per_step = 1e3 * sum(ALL[s] for s in READERS[metric]) / 5
    assert got == pytest.approx(want.get(metric, per_step))


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_none_without_its_scope(metric):
    """A renamed scope reads as nothing, not as a gain; so does a trace
    of a program with no scopes or no program spans at all."""
    read = harness.metric_reader(metric)
    if READERS[metric]:
        rest = {k: v for k, v in ALL.items() if k not in READERS[metric]}
        assert read(_run(rest)) is None
    assert read(_run({}, program_spans=0)) is None
    assert read({"trace_steps": 5}) is None      # no trace was taken
