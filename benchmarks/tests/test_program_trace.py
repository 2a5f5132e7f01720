"""``harness/program_trace.py`` on hand-built events and on a hand-written
``.xplane.pb``, and ``harness/bytes.py`` against numbers worked by hand."""
import os

import pytest

from benchmarks.harness import bytes as step_bytes
from benchmarks.harness import cells, program_trace, trace_reduce

BENCH_DIR = os.path.join(cells.REPO_ROOT, "benchmarks")
DEV, HOST, PY = "/device:TPU:0", "/host:CPU", "python3"
OPS, MODULES = "XLA Ops", "XLA Modules"
US = 1000


def _events():
    """100 us of host spans; the device runs a decode program 20..50 and
    a chunk program 60..95 with gaps 50..60 and 95..100 after them."""
    return [
        (HOST, PY, "bench.submit", 0, 10 * US, None),
        (HOST, PY, "bench.engine_step", 10 * US, 80 * US, None),
        (HOST, PY, "bench.bookkeeping", 90 * US, 10 * US, None),
        # the program's spans, flat, inside bench.engine_step
        (HOST, PY, "serving::admit", 11 * US, 4 * US, {}),
        (HOST, PY, "serving::decode_dispatch", 15 * US, 10 * US,
         {"slots": 2}),
        (HOST, PY, "serving::decode_fetch", 25 * US, 30 * US, {}),
        (HOST, PY, "serving::sample_emit", 55 * US, 3 * US, {}),
        # a user's nested pair: the inner one owns what both cover
        (HOST, PY, "user::outer", 91 * US, 8 * US, {}),
        (HOST, PY, "user::inner", 96 * US, 2 * US, {"request_id": "r1"}),
        # host noise that is nobody's span
        (HOST, PY, "PjitFunction(paged_decode_step)", 15 * US, 3 * US, None),
        (DEV, MODULES, "jit_paged_decode_step(11)", 20 * US, 30 * US, None),
        (DEV, MODULES, "jit_chunked_prefill_step(22)", 60 * US, 35 * US,
         None),
        (DEV, MODULES, "jit_paged_decode_step(11)", 99 * US, 30 * US, None),
        (DEV, OPS, "%fusion.1 = f32[8] fusion(f32[8] %p)", 20 * US, 20 * US,
         None),
        (DEV, OPS, "%copy.7 = f32[8] copy(f32[8] %p)", 40 * US, 10 * US,
         None),
        (DEV, OPS, "%while.2 = (f32[8]) while((f32[8]) %t)", 60 * US,
         30 * US, None),
        (DEV, OPS, "%fusion.1 = f32[8] fusion(f32[8] %q)", 62 * US, 20 * US,
         None),
        (DEV, OPS, "%fusion.9 = f32[8] fusion(f32[8] %q)", 90 * US, 5 * US,
         None),
    ]


SCOPES = {
    "paged_decode_step": {
        "fusion.1": ("mlp",),
        "copy.7": ("attn", "kv_write")},
    "chunked_prefill_step": {
        "while.2": ("lm_loss",),
        # the same instruction name in another program is another thing
        "fusion.1": ("lm_loss", "while", "body"),
        "fusion.9": ("mlp",)},
}


def test_the_slice_and_busy_time_are_trace_reduce_s():
    t = program_trace.ProgramTrace(_events(), SCOPES)
    r = trace_reduce.reduce_trace(
        [(p, ln, trace_reduce.short_name(n) if ln == OPS else n, s, d)
         for p, ln, n, s, d, _ in _events()])
    assert (t.busy_s, t.window_s) == (r["busy_s"], r["window_s"])
    assert t.window_s == pytest.approx(80e-6)       # 20..100
    assert t.busy_s == pytest.approx(65e-6)         # 20..50, 60..95


def test_a_gap_is_charged_to_the_innermost_program_span():
    t = program_trace.ProgramTrace(_events(), SCOPES)
    idle = t.idle_by_span
    # 50..60: fetch until 55, sample_emit 55..58, then no program span
    assert idle["serving::decode_fetch"] == pytest.approx(5e-6)
    assert idle["serving::sample_emit"] == pytest.approx(3e-6)
    # 95..100: user::outer 95..96 and 98..99, user::inner 96..98
    assert idle["user::inner"] == pytest.approx(2e-6)
    assert idle["user::outer"] == pytest.approx(2e-6)
    # 58..60 (inside bench.engine_step but no program span) and 99..100
    assert idle[program_trace.OUTSIDE] == pytest.approx(3e-6)
    assert "serving::admit" not in idle             # before the slice
    assert "bench.engine_step" not in idle          # the benchmark's own
    assert sum(idle.values()) == pytest.approx(t.window_s - t.busy_s)


def test_two_programs_on_the_modules_line():
    t = program_trace.ProgramTrace(_events(), SCOPES)
    # whole runs inside the slice only: the second decode run is cut
    assert t.program_runs == {
        "paged_decode_step": [pytest.approx(30e-6)],
        "chunked_prefill_step": [pytest.approx(35e-6)]}
    assert program_trace.program_name("jit_train_step(177818)") \
        == "train_step"
    assert program_trace.program_name("jit__lambda") == "_lambda"


def test_device_time_by_scope():
    t = program_trace.ProgramTrace(_events(), SCOPES)
    # an operation takes the scopes of ITS program's instruction
    assert t.scope_seconds("mlp") == pytest.approx(25e-6)      # 20 + 5
    assert t.scope_seconds("kv_write") == pytest.approx(10e-6)
    assert t.scope_seconds("attn") == pytest.approx(10e-6)
    # a while holds its body: a union, not a sum
    assert t.scope_seconds("lm_loss") == pytest.approx(30e-6)
    assert t.scope_seconds("body") == pytest.approx(20e-6)
    assert t.scope_seconds("optimizer_step") == 0
    rows = t.ops_by_scope
    assert rows[("paged_decode_step", "attn/kv_write", "copy")] \
        == pytest.approx(10e-6)
    assert rows[("chunked_prefill_step", "lm_loss/while/body", "fusion")] \
        == pytest.approx(20e-6)
    # without the programs' HLO nothing has a scope, and nothing fails
    bare = program_trace.ProgramTrace(_events())
    assert bare.scope_seconds("mlp") == 0
    assert bare.program_runs == t.program_runs


def test_scopes_of_an_op_name():
    scopes_of = program_trace.scopes_of
    assert scopes_of("jit(train_step)/attn_qkv/transpose(jvp())/dot_general"
                     ) == ("attn_qkv",)
    assert scopes_of("jit(paged_decode_step)/attn/kv_write/scatter") \
        == ("attn", "kv_write")
    assert scopes_of("jit(train_step)/lm_loss/jvp()/while/body/mul") \
        == ("lm_loss", "while", "body")
    assert scopes_of("jit(train_step)/optimizer_step/jit(_adamw_rule)/sub") \
        == ("optimizer_step", "jit(_adamw_rule)")
    assert scopes_of("jit(s)/attn/reshape;jit(s)/attn_qkv/squeeze") \
        == ("attn",)
    assert scopes_of("") == () and scopes_of("args[0]") == ()
    assert program_trace.instruction_name(
        "%fusion.481 = pred[1,16]{1,0:T(4,128)} fusion(s32[4] %x), "
        "kind=kLoop") == "fusion.481"


# ---- a hand-written .xplane.pb: the protobuf reader, the HLO's scopes,
# ---- and the way from a run to its trace
def _varint(n):
    out = bytearray()
    while True:
        n, b = n >> 7, n & 0x7F
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _ld(number, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _vi(number, value):
    return _varint(number << 3) + _varint(value)


def _instruction(name, opcode, op_name, uid, operands=(), calls=()):
    return _ld(2, _ld(1, name) + _ld(2, opcode)
               + (_ld(7, _ld(2, op_name)) if op_name else b"")
               + _vi(35, uid)
               + _ld(36, b"".join(_varint(i) for i in operands))
               + _ld(38, b"".join(_varint(i) for i in calls)))


def _hlo_proto():
    fused_update = _ld(3, _ld(1, "fused_computation.1") + _vi(5, 2)
                       + _instruction("p.1", "parameter", "", 10)
                       + _instruction("convert.1", "convert",
                                      "jit(s)/mlp/transpose(jvp())/convert",
                                      11, [10])
                       + _instruction("mul.1", "multiply",
                                      "jit(s)/optimizer_step/mul", 12, [11])
                       + _instruction("sub.1", "subtract",
                                      "jit(s)/optimizer_step/sub", 13, [12]))
    fused_product = _ld(3, _ld(1, "fused_computation.2") + _vi(5, 3)
                        + _instruction("conv.1", "convolution",
                                       "jit(s)/mlp/dot_general", 20)
                        + _instruction("mul.2", "multiply",
                                       "jit(s)/optimizer_step/mul", 21, [20])
                        + _instruction("add.2", "add",
                                       "jit(s)/optimizer_step/add", 22, [21]))
    entry = _ld(3, _ld(1, "main") + _vi(5, 1)
                + _instruction("fusion.1", "fusion", "jit(s)/mlp/convert",
                               1, [], [2])
                + _instruction("fusion.2", "fusion",
                               "jit(s)/optimizer_step/add", 2, [], [3])
                + _instruction("copy.7", "copy", "", 3, [1])
                + _instruction("scatter.1", "scatter",
                               "jit(s)/attn/kv_write/scatter", 4, [3])
                + _instruction("dot.3", "dot", "jit(s)/dot_general", 5)
                + _instruction("add.9", "add", "jit(s)/optimizer_step/add",
                               6, [5]))
    return _ld(1, _ld(1, "jit_s") + fused_update + fused_product + entry)


def _text_bytes(raw):
    return '"' + "".join("\\%03o" % b for b in raw) + '"'


def _write_xplane(path):
    from jax.profiler import ProfileData

    text = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 30000000 } }
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 30000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 40000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_s(77)" } }
  event_metadata { key: 2 value { id: 2
    name: "%%fusion.1 = f32[8] fusion(f32[8] %%p)" } }
  event_metadata { key: 3 value { id: 3
    name: "%%fusion.2 = f32[8] fusion(f32[8] %%p)" } }
  event_metadata { key: 4 value { id: 4
    name: "%%copy.7 = f32[8] copy(f32[8] %%fusion.1)" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 50000000 }
    events { metadata_id: 2 offset_ps: 12000000 duration_ps: 45000000
      stats { metadata_id: 1 str_value: "r1" } } }
  event_metadata { key: 1 value { id: 1 name: "bench.engine_step" } }
  event_metadata { key: 2 value { id: 2 name: "serving::decode_fetch" } }
  stat_metadata { key: 1 value { id: 1 name: "request_id" } }
}
planes {
  name: "/host:metadata"
  event_metadata { key: 1 value { id: 1 name: "jit_s(77)"
    stats { metadata_id: 1 bytes_value: %s } } }
  stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } }
}
""" % _text_bytes(_hlo_proto())
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))


@pytest.fixture
def traced_root(tmp_path):
    d = tmp_path / ".bench_trace" / "a-cell" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    _write_xplane(str(d / "vm.xplane.pb"))
    return str(tmp_path), str(d / "vm.xplane.pb")


def test_the_hlo_s_scopes_are_read_from_the_metadata_plane(traced_root):
    _, path = traced_root
    scopes = program_trace.hlo_scopes(path)["s"]
    # AdamW's fusion with a stray cast of the gradient: most instructions
    assert scopes["fusion.1"] == ("optimizer_step",)
    # an update fused behind the gradient's product: the product's scope
    assert scopes["fusion.2"] == ("mlp",)
    # the compiler's own copy: its user's scopes
    assert scopes["copy.7"] == ("attn", "kv_write")
    assert scopes["scatter.1"] == ("attn", "kv_write")
    # the program's own instruction outside every scope: not its user's
    assert scopes["dot.3"] == () and scopes["add.9"] == ("optimizer_step",)


def test_a_written_trace_is_read_from_the_path_the_run_carries(traced_root):
    root, path = traced_root
    events = program_trace.read_events(path)
    assert ("/host:CPU", "python3", "serving::decode_fetch", 12000, 45000,
            {"request_id": "r1"}) in events
    mine = trace_reduce.reduce_trace(trace_reduce.read_xplane(path))
    # the gap 50..60 falls in the program's span until 57, then in the
    # benchmark's around it: the breakdown names the innermost
    assert dict(mine["idle_gaps"]) == {
        "serving::decode_fetch": pytest.approx(7e-6),
        "bench.engine_step": pytest.approx(3e-6)}
    run = {"trace": mine, "trace_path": path}
    t = program_trace.load(run)
    assert t is not None and t is program_trace.load(run)   # parsed once
    assert (t.busy_s, t.window_s) == (mine["busy_s"], mine["window_s"])
    assert t.window_s == pytest.approx(40e-6)       # 20..60
    assert t.idle_by_span == {
        "serving::decode_fetch": pytest.approx(7e-6),      # 50..57
        program_trace.OUTSIDE: pytest.approx(3e-6)}
    assert t.program_runs == {"s": [pytest.approx(30e-6)]}
    assert t.scope_seconds("optimizer_step") == pytest.approx(10e-6)
    assert t.scope_seconds("kv_write") == pytest.approx(10e-6)
    # a run that was not traced, or whose file is gone: nothing is read,
    # and no other file under .bench_trace/ is looked for
    assert program_trace.load({"trace": mine}) is None
    assert program_trace.load({"trace": None, "trace_path": None}) is None
    assert program_trace.load(
        {"trace": mine, "trace_path": os.path.join(root, "nowhere.pb")}
    ) is None
    for reader in (program_trace.program_ms, program_trace.scope_pct):
        assert reader({"trace": None}, "s") is None
    assert program_trace.idle_pct_inside({}, ("serving::admit",)) is None


def test_least_bytes_at_the_mistral_widths():
    serve = cells.load_json(os.path.join(
        BENCH_DIR, "configs", "mistral-7b-v0.3-serve.json"))
    layer = 218103808           # as ISSUE 25: the four attention and
    head = 4096 * 32768         # three MLP matrices; the output head
    assert serve["num_hidden_layers"] == 16
    # keys and values, 16 layers, 8 heads of 128, bf16: 64 KiB a token
    assert step_bytes.kv_bytes_per_token(serve) == 2 * 16 * 8 * 128 * 2 \
        == 65536
    weights = (16 * layer + head) * 2
    assert weights == 7247757312
    assert step_bytes.decode_step_bytes(serve, 0) == weights
    # 32 sequences of 600 tokens: 1.26 GB of cache beside 7.25 of weights
    assert step_bytes.decode_step_bytes(serve, 32 * 600) \
        == weights + 19200 * 65536 == 8506048512
