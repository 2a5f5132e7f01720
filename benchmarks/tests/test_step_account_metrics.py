"""The five readers of the engine's step account (ISSUE 38) on hand-made
runs, the ``serving::step`` span in the two reductions of a trace, and
the readers' entries in ``BENCHMARK.json``."""
import os

import pytest

from benchmarks.harness import cells, program_trace, trace_reduce

BENCH_DIR = os.path.join(cells.REPO_ROOT, "benchmarks")
NAMES = ("slow_step_time_pct", "host_offcpu_ms_per_iter",
         "step_unowned_us_per_iter", "programs_behind_read_mean",
         "idle_between_phases_pct")
SERVING_CELLS = [
    "mistral7b-serve.closed32-chat", "mistral7b-serve.closed8-longprompt",
    "sdar30b-serve.closed32-gen256", "trinity-mini-serve.closed16-longctx",
    "glm47flash-serve.closed8-docreask"]
DEV, HOST, PY = "/device:TPU:0", "/host:CPU", "python3"
OPS, MODULES = "XLA Ops", "XLA Modules"
US = 1000


def _reader(name):
    return cells.load_module(
        os.path.join(BENCH_DIR, "layer_metrics", name + ".py"), name)


# 2,000 steps of 25 ms, one of them a stall of 0.4 s beyond the usual
# step; the parent's counters are the dict without the new keys
PARENT = {
    "engine_steps": 2000,
    "step_ns.admit": 40_000_000, "step_ns.prefill_dispatch": 900_000_000,
    "step_ns.first_token": 1_000_000_000,
    "step_ns.decode_prepare": 200_000_000,
    "step_ns.decode_dispatch": 2_400_000_000,
    "step_ns.decode_fetch": 44_000_000_000,
    "step_ns.sample_emit": 1_300_000_000, "step_ns.pool_sync": 10_000_000}
CHANGE = dict(
    PARENT, step_wall_ns=50_000_000_000, step_cpu_ns=4_820_000_000,
    step_nivcsw=12, step_minflt=3,
    programs_dispatched=2700, blocking_reads=2200,
    programs_behind_reads=2700, slow_steps=1,
    slow_step_excess_ns=400_000_000)


@pytest.mark.parametrize("name, value", [
    ("slow_step_time_pct", 0.8),                # 0.4 s of 50 s
    # 50 s less 45 s in the two waits is 5 s of the engine's own code,
    # and the whole steps took 4.82 s of CPU time
    ("host_offcpu_ms_per_iter", 0.09),
    ("step_unowned_us_per_iter", 75.0),         # 50 s - 49.85 s owned
    ("programs_behind_read_mean", 2700 / 2200),
])
def test_a_counter_reader_on_a_hand_made_run(name, value):
    read = _reader(name).read
    assert read({"counters": CHANGE}) == pytest.approx(value)
    # the parent under this benchmark, a training run, a window with no
    # step in it: nothing to read, and nothing raised
    assert read({"counters": PARENT}) is None
    assert read({"counters": dict.fromkeys(CHANGE, 0)}) is None
    assert read({"counters": {}}) is None and read({}) is None


def test_no_stall_reads_zero_not_none():
    # (the steps' CPU time holds what the thread burnt inside its waits)
    run = {"counters": dict(CHANGE, slow_steps=0, slow_step_excess_ns=0,
                            step_cpu_ns=5_400_000_000)}
    assert _reader("slow_step_time_pct").read(run) == 0.0
    assert _reader("host_offcpu_ms_per_iter").read(run) == 0.0


def _events(step_span=True):
    """The device runs 10..30 and 70..80 of a 100 us slice.  One
    ``Engine.step()`` 5..95 holds ``admit`` 6..16, ``decode_fetch``
    20..50 and ``sample_emit`` 56..60; 50..56 and 60..70 lie between
    phases."""
    spans = [
        (HOST, PY, "bench.submit", 0, 4 * US, None),
        (HOST, PY, "bench.engine_step", 4 * US, 92 * US, None),
        (HOST, PY, "bench.bookkeeping", 96 * US, 4 * US, None),
        (HOST, PY, "serving::admit", 6 * US, 10 * US, {}),
        (HOST, PY, "serving::decode_fetch", 20 * US, 30 * US, {}),
        (HOST, PY, "serving::sample_emit", 56 * US, 4 * US, {}),
    ]
    if step_span:
        spans.append((HOST, PY, "serving::step", 5 * US, 90 * US,
                      {"step": 7}))
    return spans + [
        (DEV, MODULES, "jit_paged_decode_step(11)", 10 * US, 20 * US, None),
        (DEV, OPS, "%fusion.1 = f32[8] fusion(f32[8] %p)", 10 * US, 20 * US,
         None),
        (DEV, OPS, "%fusion.2 = f32[8] fusion(f32[8] %p)", 70 * US, 10 * US,
         None),
    ]


def test_innermost_gives_the_step_what_lies_between_its_phases():
    spans = [(s, s + d, n) for _, _, n, s, d, st in _events()
             if st is not None]
    pieces = trace_reduce.innermost(spans)
    assert pieces == [
        (5 * US, 6 * US, "serving::step"),
        (6 * US, 16 * US, "serving::admit"),
        (16 * US, 20 * US, "serving::step"),
        (20 * US, 50 * US, "serving::decode_fetch"),
        (50 * US, 56 * US, "serving::step"),
        (56 * US, 60 * US, "serving::sample_emit"),
        (60 * US, 95 * US, "serving::step")]


def test_the_phases_keep_their_gaps_and_the_step_owns_the_rest():
    with_step = program_trace.ProgramTrace(_events()).idle_by_span
    without = program_trace.ProgramTrace(_events(False)).idle_by_span
    # the slice is 10..100 (from the first device operation): the device
    # idles 30..70 and 80..100
    for phase, seconds in (("serving::decode_fetch", 20e-6),
                           ("serving::sample_emit", 4e-6)):
        assert with_step[phase] == without[phase] == pytest.approx(seconds)
    assert "serving::admit" not in with_step        # the device was busy
    # 50..56, 60..70 and 80..95 of the step; 95..100 outside it
    assert with_step["serving::step"] == pytest.approx(31e-6)
    assert with_step[program_trace.OUTSIDE] == pytest.approx(5e-6)
    assert without[program_trace.OUTSIDE] == pytest.approx(36e-6)
    assert sum(with_step.values()) == pytest.approx(60e-6) \
        == pytest.approx(sum(without.values()))


def test_the_ledger_s_breakdown_names_the_step_where_the_bench_span_was():
    def reduced(step_span):
        return dict(trace_reduce.reduce_trace(
            [(p, ln, trace_reduce.short_name(n) if ln == OPS else n, s, d)
             for p, ln, n, s, d, _ in _events(step_span)])["idle_gaps"])

    assert reduced(True)["serving::step"] == pytest.approx(31e-6)
    assert reduced(True)["bench.bookkeeping"] == pytest.approx(4e-6)
    assert reduced(True)["bench.engine_step"] == pytest.approx(1e-6)
    assert reduced(False)["bench.engine_step"] == pytest.approx(32e-6)


def test_idle_between_phases_reads_the_step_s_own_gaps(monkeypatch):
    read = _reader("idle_between_phases_pct").read
    traces = {"change": program_trace.ProgramTrace(_events()),
              "parent": program_trace.ProgramTrace(_events(False))}
    monkeypatch.setattr(program_trace, "load",
                        lambda run: traces.get(run.get("trace_path")))
    assert read({"trace_path": "change"}) == pytest.approx(100 * 31 / 90)
    # the parent's trace holds the phases and no step: nothing to read
    assert read({"trace_path": "parent"}) is None
    assert read({}) is None
    # with the three accepted shares and the gaps outside the step it
    # sums to the device's idle share of the slice
    idle = sum(program_trace.idle_pct_inside({"trace_path": "change"}, spans)
               for spans in (("serving::decode_fetch", "serving::first_token"),
                             ("serving::sample_emit",),
                             ("serving::admit", "serving::pool_sync")))
    trace = traces["change"]
    assert idle + read({"trace_path": "change"}) + 100 * 5 / 90 \
        == pytest.approx(100 * (1 - trace.busy_s / trace.window_s))


def test_the_five_entries_are_in_the_benchmark_and_load_for_every_cell():
    entries = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    assert list(entries)[-5:] == list(NAMES)
    for name in NAMES:
        entry = entries[name]
        assert entry["workloads"] == SERVING_CELLS
        assert entry["moves"] == "serve_tok_s"
        assert entry["layer"] == ("device" if name.startswith("idle_")
                                  else "scheduler")
    for cell_name in SERVING_CELLS:
        cell = cells.load_cell(cell_name)
        assert set(NAMES) <= set(cell.readers)
    training = cells.load_cell("mistral7b-train.pretrain-seq2k")
    assert not set(NAMES) & set(training.readers)
