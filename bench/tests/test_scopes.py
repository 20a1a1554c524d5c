"""Device time by named scope (`bench/scopes.py`) and its four readers,
on a window recorded on one TPU v5 lite by `record_scoped.py`: a few steps
of the train cell at the smoke size (`data/scoped.xplane.pb`) and the
step's compiled HLO (`data/scoped_hlo.txt`)."""

import re
from pathlib import Path

import pytest
import smoke

import flops
import harness
import scopes
import trace_reduce

DATA = Path(__file__).resolve().parent / "data"
PEAK = harness.load_json(harness.BENCH / "peaks.json")["devices"][
    "TPU v5 lite"]
READERS = ("lattice_query_ms.train", "table_gather_ms.train",
           "gather_roofline.train", "optimizer_ms.train")

HLO = """HloModule jit_f, is_scheduled=true

%fused (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %inner = f32[4]{0} sine(%p), metadata={op_name="jit(f)/lram/query/sin"}
}

%body (c: (s32[], f32[4])) -> (s32[], f32[4]) {
  %c = (s32[], f32[4]{0}) parameter(0)
  ROOT %step = (s32[], f32[4]{0}) tuple(%c), metadata={op_name="jit(f)/while/body/optim/add"}
}

%cond (c: (s32[], f32[4])) -> pred[] {
  %c = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt = pred[] constant(true), metadata={op_name="jit(f)/while/cond/lt"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/transpose(jvp(lram))/gather/jit(_take)/scatter-add"}
  %tuple = (s32[], f32[4]{0}) tuple(%fusion.1)
  %while.2 = (s32[], f32[4]{0}) while(%tuple), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
  ROOT %copy.3 = f32[4]{0} copy(%fusion.1)
}
"""


def test_split_path_unwraps_transformations():
    assert scopes.split_path(
        "jit(train_step)/transpose(jvp(lram))/gather/jit(_take)/scatter-add"
    ) == ["jit(train_step)", "lram", "gather", "jit(_take)", "scatter-add"]
    assert scopes.split_path("jit(f)/jvp()/vmap(a/b)/remat(c)/x") == \
        ["jit(f)", "a", "b", "c", "x"]
    segs = scopes.split_path("jit(f)/jvp(lram)/query/top_k/top_k")
    assert scopes.under(segs, "lram/query")
    assert scopes.under(segs, "query/top_k")
    assert not scopes.under(segs, "lram/gather")
    assert not scopes.under(segs, "query/lram")


def test_op_scopes_takes_the_entry_and_what_it_runs_as_ops():
    got = scopes.op_scopes(HLO)
    # the fusion by its own metadata, not its fused instructions'; while
    # bodies and conditions run as ops; instructions without op_name out
    assert set(got) == {"x", "fusion.1", "while.2", "step", "lt"}
    assert got["fusion.1"]["entry"] and not got["step"]["entry"]
    assert scopes.under(got["fusion.1"]["segments"], "lram/gather")
    assert scopes.device_computations(HLO) == ["main", "body", "cond"]


def test_scope_seconds_counts_executions_by_entry_ops():
    trace = {"ops": {
        "%fusion.1 = f32[4]{0} fusion(%x)": {"s": 0.3, "count": 3},
        "%step = (s32[], f32[4]{0}) tuple(%c)": {"s": 0.9, "count": 30},
        "%other = f32[4]{0} add(%x, %x)": {"s": 5.0, "count": 3}}}
    ctx = {"trace": trace, "hlo": HLO}
    assert scopes.scope_seconds(ctx, "lram/gather") == (0.3, 3)
    # only a while body's ops matched: their own count is all there is
    assert scopes.scope_seconds(ctx, "optim") == (0.9, 30)
    assert scopes.scope_ms_per_step(ctx, "lram/gather") == pytest.approx(100)
    assert scopes.scope_seconds(ctx, "lram/query") is None


@pytest.fixture(scope="module")
def recorded():
    hlo = (DATA / "scoped_hlo.txt").read_text()
    trace = trace_reduce.reduce(str(DATA / "scoped.xplane.pb"), 1)
    cell = smoke.smoke_cell("train-bert-medium-mlm128")
    mix = cell["traffic"]
    inputs = {"tokens_per_step": mix["batch"] * mix["seq_len"]}
    return {"cell": cell, "inputs": inputs, "trace": trace, "peak": PEAK,
            "flops": flops, "hlo": hlo}


def test_fixture_is_small():
    size = sum((DATA / f).stat().st_size
               for f in ("scoped_hlo.txt", "scoped.xplane.pb"))
    assert size < 1_000_000


def test_recorded_hlo_names_the_scopes(recorded):
    ops = scopes.op_scopes(recorded["hlo"])
    for scope in ("lram/query_norm", "lram/query", "lram/gather", "optim",
                  "optim/clip", "query/decode", "query/distance",
                  "query/undo_isometry", "query/top_k", "query/encode"):
        assert any(scopes.under(v["segments"], scope) for v in ops.values()), \
            scope
    assert any(v["path"].startswith("jit(train_step)/transpose(")
               and v["path"].endswith("scatter-add")
               and scopes.under(v["segments"], "lram/gather")
               for v in ops.values())


def test_recorded_seconds_by_scope(recorded):
    trace = recorded["trace"]
    got = {s: scopes.scope_seconds(recorded, s)
           for s in ("lram/query", "lram/gather", "optim")}
    assert all(v is not None and v[0] > 0 for v in got.values()), got
    steps = {v[1] for v in got.values()}
    assert len(steps) == 1 and steps.pop() >= 2
    # disjoint scopes: their sum is device time, within the busy time
    assert sum(v[0] for v in got.values()) <= trace["busy_s"]


def test_readers_read_the_recorded_window(recorded):
    got = {m: harness.reader(m).read(recorded) for m in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["gather_roofline.train"] < 100
    seconds, steps = scopes.scope_seconds(recorded, "lram/gather")
    need = flops.lookup_bytes(recorded["cell"]["config"],
                              recorded["inputs"]["tokens_per_step"],
                              backward=True)
    assert got["gather_roofline.train"] == pytest.approx(
        100 * need / PEAK["hbm_bytes_per_s"] / (seconds / steps))
    assert got["table_gather_ms.train"] == pytest.approx(
        1e3 * seconds / steps)


def test_readers_read_nothing_without_scopes(recorded, tmp_path,
                                             monkeypatch):
    """A program without the scopes (as before they were named), or a
    profile that holds no program text: no reading, and no error."""
    monkeypatch.setattr(harness, "trace_dir", lambda: tmp_path)
    bare = re.sub(r', metadata=\{[^}]*\}', "", recorded["hlo"])
    no_text = {k: v for k, v in recorded.items() if k != "hlo"}
    for ctx in (dict(recorded, hlo=bare), no_text):
        assert all(harness.reader(m).read(ctx) is None for m in READERS)
    (tmp_path / "w.xplane.pb").write_bytes(
        (DATA / "scoped.xplane.pb").read_bytes())
    assert scopes.program_hlo(no_text) is None
    assert all(harness.reader(m).read(no_text) is None for m in READERS)


def _scoped_program():
    """A jitted function with a named scope, compiled on the CPU."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("lram"):
            with jax.named_scope("gather"):
                return jnp.sin(x) * 2

    return f, jnp.ones(8)


def test_hlo_protos_reads_the_programs_of_a_profile(tmp_path):
    import jax

    f, x = _scoped_program()
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    protos = scopes.hlo_protos(path.read_bytes())
    name = next(n for n in protos if n.startswith("jit_f("))
    text = scopes.module_text(protos[name])
    assert text.startswith("HloModule jit_f")
    assert any(scopes.under(v["segments"], "lram/gather")
               for v in scopes.op_scopes(text).values())
    assert scopes.hlo_protos(b"") == {}


def _ld(number: int, payload: bytes) -> bytes:
    """A length-delimited protobuf field."""
    def varint(n):
        out = b""
        while n >= 0x80:
            out += bytes([n & 0x7F | 0x80])
            n >>= 7
        return out + bytes([n])

    return varint(number << 3 | 2) + varint(len(payload)) + payload


def test_program_hlo_reads_the_longest_program_of_the_runs_profile(
        tmp_path, monkeypatch):
    """The recorded window with a `/host:metadata` plane put back, which
    holds a program under the name of the window's longest: the run's
    profile gives that program's text, and `ctx['hlo']` comes first."""
    from jax.profiler import ProfileData

    raw = (DATA / "scoped.xplane.pb").read_bytes()
    longest = scopes.longest_program(ProfileData.from_serialized_xspace(raw))
    assert longest.startswith("jit_train_step(")
    f, x = _scoped_program()
    module = f.lower(x).compile().runtime_executable().hlo_modules()[0] \
        .as_serialized_hlo_module_proto()
    one = bytes([8, 1])                       # field 1 (id), varint 1
    stat = _ld(5, one + _ld(6, _ld(1, module)))
    plane = (_ld(2, scopes.METADATA_PLANE)
             + _ld(4, bytes([8, 7]) + _ld(2, _ld(2, longest.encode())
                                           + stat))
             + _ld(5, one + _ld(2, one + _ld(2, scopes.HLO_PROTO_STAT))))
    (tmp_path / "w.xplane.pb").write_bytes(raw + _ld(1, plane))
    monkeypatch.setattr(harness, "trace_dir", lambda: tmp_path)
    text = scopes.program_hlo({"trace": {}})
    assert text is not None and text.startswith("HloModule jit_f")
    assert 'op_name="jit(f)/lram/gather/' in text
    assert scopes.program_hlo({"hlo": "given"}) == "given"


def test_recorder_drops_a_plane_and_keeps_the_rest(tmp_path):
    import record_scoped

    raw = (DATA / "scoped.xplane.pb").read_bytes()
    assert record_scoped.drop_plane(raw, "no such plane") == raw
    path = tmp_path / "x.xplane.pb"
    path.write_bytes(record_scoped.drop_plane(raw, "/host:CPU"))
    planes = {p.name for p in trace_reduce.load(str(path)).planes}
    assert "/host:CPU" not in planes and "/device:TPU:0" in planes
    assert trace_reduce.reduce(str(path), 1)["busy_s"] > 0
