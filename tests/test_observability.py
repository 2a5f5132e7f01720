"""paddle_tpu.observability: registry semantics, exporters, compile/
retrace accounting, step timing, and the producer mirrors (serving,
resilience, hapi fit, profiler fallback)."""
import json
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, observability as obs
from paddle_tpu.observability import (Counter, Gauge, Histogram,
                                      MetricsRegistry, RetraceError,
                                      RetraceWarning)


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test sees an empty default registry and disabled telemetry."""
    obs.get_registry().clear()
    prev = obs.enable(False)
    yield
    obs.enable(prev)
    obs.get_registry().clear()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_inc_and_labels(self):
        reg = MetricsRegistry()
        c = Counter("requests_total", "reqs", registry=reg)
        c.inc()
        c.inc(2.5, route="a")
        c.inc(route="a")
        assert c.value() == 1.0
        assert c.value(route="a") == 3.5
        assert c.value(route="missing") == 0.0

    def test_counter_rejects_negative(self):
        c = Counter("c_total", registry=MetricsRegistry())
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        g = Gauge("g", registry=MetricsRegistry())
        g.set(5)
        g.inc(2)
        g.dec(3)
        assert g.value() == 4.0

    def test_histogram_bucketing(self):
        h = Histogram("h_seconds", buckets=(0.1, 1.0, 10.0),
                      registry=MetricsRegistry())
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        snap = h.snapshot()
        cell = snap.series[()]
        assert cell["buckets"] == [1, 1, 1, 1]     # one per bucket + +Inf
        assert cell["count"] == 4
        assert cell["sum"] == pytest.approx(55.55)
        assert snap.boundaries == (0.1, 1.0, 10.0)

    def test_histogram_boundary_validation(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="ascending"):
            Histogram("h1", buckets=(1.0, 0.5), registry=reg)
        with pytest.raises(ValueError, match="at least one"):
            Histogram("h2", buckets=(), registry=reg)
        # a trailing +Inf is accepted and stripped (it's implicit)
        h = Histogram("h3", buckets=(1.0, float("inf")), registry=reg)
        assert h.boundaries == (1.0,)

    def test_invalid_names_rejected(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="invalid metric name"):
            Counter("bad name", registry=reg)
        c = Counter("ok_total", registry=reg)
        with pytest.raises(ValueError, match="invalid label name"):
            c.inc(**{"bad-label": "x"})

    def test_duplicate_name_and_kind_mismatch(self):
        reg = MetricsRegistry()
        reg.counter("m")
        assert reg.counter("m") is reg.counter("m")     # get-or-create
        with pytest.raises(TypeError, match="is a counter"):
            reg.gauge("m")
        with pytest.raises(ValueError, match="already registered"):
            Counter("m", registry=reg)
        reg.histogram("h", buckets=(1.0, 2.0))
        with pytest.raises(ValueError, match="buckets are fixed"):
            reg.histogram("h", buckets=(1.0, 3.0))

    def test_cardinality_cap_folds_to_overflow(self):
        reg = MetricsRegistry()
        c = Counter("capped_total", registry=reg, max_series=3)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for i in range(10):
                c.inc(user=f"u{i}")
            overflow_warns = [x for x in w
                              if "label-cardinality" in str(x.message)]
        assert len(overflow_warns) == 1                 # warned ONCE
        assert c.labels_count() == 4                    # 3 real + overflow
        assert c.value(overflow="true") == 7.0

    def test_collect_sorted_and_consistent(self):
        reg = MetricsRegistry()
        reg.counter("b_total").inc()
        reg.gauge("a").set(1)
        reg.histogram("c_seconds", buckets=(1.0,)).observe(0.5)
        snaps = reg.collect()
        assert [s.name for s in snaps] == ["a", "b_total", "c_seconds"]
        assert [s.kind for s in snaps] == ["gauge", "counter", "histogram"]
        # snapshots are copies: mutating after collect changes nothing
        reg.counter("b_total").inc(100)
        assert snaps[1].series[()] == 1.0

    def test_enable_returns_previous_state(self):
        assert obs.enabled() is False
        assert obs.enable(True) is False
        assert obs.enabled() is True
        assert obs.enable(True) is True
        assert obs.disable() is True
        assert obs.enabled() is False


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

class TestExporters:
    def _sample_registry(self):
        reg = MetricsRegistry()
        reg.counter("req_total", "requests").inc(3, route="a")
        reg.gauge("occ", "occupancy").set(0.5)
        h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        return reg

    def test_prometheus_text_format(self):
        text = obs.prometheus_text(self._sample_registry())
        assert "# HELP req_total requests\n# TYPE req_total counter" in text
        assert 'req_total{route="a"} 3' in text
        # histogram: cumulative buckets, +Inf == count, sum and count
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="1"} 2' in text
        assert 'lat_seconds_bucket{le="+Inf"} 3' in text
        assert "lat_seconds_count 3" in text
        assert "lat_seconds_sum 5.55" in text

    def test_label_value_escaping(self):
        reg = MetricsRegistry()
        reg.counter("c_total").inc(path='a"b\\c\nd')
        text = obs.prometheus_text(reg)
        assert r'path="a\"b\\c\nd"' in text

    def test_json_export(self, tmp_path):
        reg = self._sample_registry()
        blob = obs.to_json(reg)
        assert {m["name"] for m in blob["metrics"]} == \
            {"req_total", "occ", "lat_seconds"}
        hist = [m for m in blob["metrics"]
                if m["name"] == "lat_seconds"][0]
        assert hist["boundaries"] == [0.1, 1.0]
        assert hist["series"][0]["count"] == 3
        path = obs.write_json(str(tmp_path / "m.json"), reg)
        assert json.load(open(path))["metrics"] == blob["metrics"]

    def test_file_sink_dump_and_enable_lifecycle(self, tmp_path):
        reg = self._sample_registry()
        sink = obs.FileSink(str(tmp_path), interval_s=None, registry=reg)
        assert obs.enabled() is False
        with sink:
            assert obs.enabled() is True        # start() armed telemetry
            out = sink.dump()
        assert obs.enabled() is False           # stop() restored it
        assert sink.writes >= 2                 # explicit + final dump
        assert "req_total" in open(out["prom"]).read()
        assert os.path.exists(sink.json_path)

    def test_file_sink_periodic_thread(self, tmp_path):
        import time

        reg = self._sample_registry()
        sink = obs.FileSink(str(tmp_path), interval_s=0.02, registry=reg)
        sink.start()
        deadline = time.monotonic() + 5.0
        while sink.writes < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        sink.stop()
        assert sink.writes >= 2
        assert os.path.exists(sink.prom_path)


# ---------------------------------------------------------------------------
# compile tracker
# ---------------------------------------------------------------------------

class TestCompileTracker:
    def test_track_compiles_counts_cache_growth(self):
        import jax
        import jax.numpy as jnp

        f = obs.track_compiles(jax.jit(lambda x: x * 2), label="toy")
        f(jnp.ones((2,)))
        f(jnp.ones((2,)))           # cache hit: no compile
        f(jnp.ones((3,)))           # new shape: compile
        assert f.calls == 3
        assert f.compiles == 2
        assert f.compile_seconds > 0
        assert f.cache_size() == 2
        assert f._cache_size() == 2          # engine-compat alias
        assert obs.compile_stats()["toy"]["compiles"] == 2

    def test_tracks_to_static_functions(self):
        from paddle_tpu import jit

        @jit.to_static
        def step(x):
            return x + 1

        tracked = obs.track_compiles(step, label="static_toy")
        tracked(paddle.to_tensor(np.zeros((2,), np.float32)))
        tracked(paddle.to_tensor(np.zeros((3,), np.float32)))
        assert tracked.compiles == 2

    def test_untrackable_fn_rejected(self):
        with pytest.raises(TypeError, match="cannot read a jit cache"):
            obs.track_compiles(lambda x: x)

    def test_registry_mirror_when_enabled(self):
        import jax
        import jax.numpy as jnp

        obs.enable(True)
        f = obs.track_compiles(jax.jit(lambda x: x + 1), label="mirror")
        f(jnp.ones((2,)))
        reg = obs.get_registry()
        assert reg.counter("xla_compiles_total").value(fn="mirror") == 1
        assert reg.get("xla_compile_seconds_total") is not None
        assert reg.gauge("xla_jit_cache_entries").value(fn="mirror") == 1

    def test_warn_on_retrace_shape_churn(self):
        """A shape-churning toy fn trips the guard past its allowance."""
        import jax
        import jax.numpy as jnp

        g = obs.warn_on_retrace(jax.jit(lambda x: x.sum()), after=1,
                                label="churny")
        g(jnp.ones((2,)))                       # warmup compile: allowed
        g(jnp.ones((2,)))                       # cache hit: fine
        with pytest.warns(RetraceWarning, match="H101"):
            g(jnp.ones((3,)))                   # retrace -> warns
        assert g.retraces == 1

    def test_warn_on_retrace_raise_mode(self):
        import jax
        import jax.numpy as jnp

        g = obs.warn_on_retrace(jax.jit(lambda x: x + 1), after=1,
                                on_retrace="raise")
        g(jnp.ones((2,)))
        with pytest.raises(RetraceError, match="retraced after warmup"):
            g(jnp.ones((4,)))

    def test_warn_on_retrace_count_mode(self):
        import jax
        import jax.numpy as jnp

        g = obs.warn_on_retrace(jax.jit(lambda x: x + 1), after=0,
                                on_retrace="count")
        with warnings.catch_warnings():
            warnings.simplefilter("error")      # counting must not warn
            g(jnp.ones((2,)))
            g(jnp.ones((3,)))
        assert g.retraces == 2

    def test_serving_decode_step_exact_compile_count(self):
        """The PR 2 no-retrace test, upgraded: across staggered
        admit/retire cycles the bucketed decode step records EXACTLY one
        compile through the engine's tracked wrapper, and zero
        retraces."""
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import Engine, ServingConfig

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        model.eval()
        eng = Engine(model, ServingConfig(max_batch_size=2, block_size=8,
                                          num_blocks=32))
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 256, size=(n,)).astype(np.int32)
                   for n in (3, 8, 5, 6)]      # > slots: admit/retire churn
        for p in prompts:                       # staggered arrivals
            eng.submit(p, max_new_tokens=6)
            eng.step()
        eng.run_until_complete()
        assert eng.metrics.completed == 4
        assert eng._decode_step.compiles == 1   # ONE warmup compile
        assert eng._decode_step.retraces == 0
        assert eng.decode_cache_size() == 1     # public contract intact
        # prefill compiled once per distinct bucketed prompt length
        assert eng._prefill_step.compiles >= 1


# ---------------------------------------------------------------------------
# step timer
# ---------------------------------------------------------------------------

class TestStepTimer:
    def test_accounting_without_registry(self):
        t = obs.StepTimer()
        data = [np.zeros((2, 8)) for _ in range(3)]
        seen = []
        for i, b in t.timed_enumerate(data):
            seen.append(i)
            t.step(loss=1.5, inputs=b)
        assert seen == [0, 1, 2]
        s = t.summary()
        assert s["steps"] == 3
        assert s["tokens"] == 3 * 16
        assert s["last_loss"] == 1.5
        assert s["steps_per_sec"] > 0
        assert 0.0 <= s["data_fraction"] <= 1.0
        # disabled: nothing leaked into the default registry
        assert obs.get_registry().names() == []

    def test_registry_mirror(self):
        obs.enable(True)
        t = obs.StepTimer()
        for i, b in t.timed_enumerate([np.zeros((2, 4))] * 2):
            t.step(loss=0.25, inputs=b)
        reg = obs.get_registry()
        assert reg.counter("train_steps_total").value() == 2
        assert reg.counter("train_tokens_total").value() == 16
        assert reg.gauge("train_loss").value() == 0.25
        hist = reg.get("train_step_seconds")
        assert hist.count(phase="data") == 2
        assert hist.count(phase="device") == 2
        assert hist.count(phase="total") == 2

    def test_count_tokens_shapes(self):
        assert obs.count_tokens(np.zeros((4, 8))) == 32
        assert obs.count_tokens([np.zeros((2, 3)), np.zeros((9,))]) == 6
        assert obs.count_tokens({"ids": np.zeros((5,))}) == 5
        assert obs.count_tokens(paddle.to_tensor(np.zeros((2, 4)))) == 8
        assert obs.count_tokens("not an array") == 0
        assert obs.count_tokens([]) == 0

    def test_fit_wires_timer_when_enabled(self):
        net = nn.Linear(4, 2)
        model = paddle.Model(net)
        model.prepare(paddle.optimizer.SGD(
            0.1, parameters=net.parameters()), nn.MSELoss())
        rng = np.random.RandomState(0)
        batches = [(rng.randn(2, 4).astype(np.float32),
                    rng.randn(2, 2).astype(np.float32))
                   for _ in range(4)]
        obs.enable(True)
        model.fit(train_data=batches, epochs=1, verbose=0)
        reg = obs.get_registry()
        assert reg.counter("train_steps_total").value() == 4
        assert reg.get("train_step_seconds").count(phase="total") == 4
        # the tracked train step reported its compile
        assert reg.counter("xla_compiles_total").value(
            fn="hapi::train_step") >= 1

    def test_fit_no_op_when_disabled(self):
        net = nn.Linear(4, 2)
        model = paddle.Model(net)
        model.prepare(paddle.optimizer.SGD(
            0.1, parameters=net.parameters()), nn.MSELoss())
        rng = np.random.RandomState(0)
        batches = [(rng.randn(2, 4).astype(np.float32),
                    rng.randn(2, 2).astype(np.float32))]
        model.fit(train_data=batches, epochs=1, verbose=0)
        assert obs.get_registry().names() == []


# ---------------------------------------------------------------------------
# serving mirror
# ---------------------------------------------------------------------------

class TestServingMirror:
    _CONTRACT_COUNTERS = {
        "requests_submitted", "requests_rejected", "requests_completed",
        "requests_timed_out", "requests_failed", "requests_shed",
        "preemptions", "tokens_generated", "goodput_tokens",
        "decode_iterations", "prefills",
        "prefix_cache_hits", "prefix_cache_misses",
        "prefix_cache_evictions", "prefill_chunks",
        "watchdog_stalls", "step_retries", "pool_lost",
        "spec_tokens_drafted", "spec_tokens_accepted",
        # inside Engine.step() (ISSUE 26)
        "engine_steps", "prefill_steps", "prefill_chunks_run",
        "decode_context_tokens", "prompt_tokens", "cached_prompt_tokens",
        "admissions", "queue_wait_ns", "lane_wait_ns",
        # what the chunk kernel's walk covers (ISSUE 31)
        "prefill_context_tokens",
        # the (query, key) pairs the chunks attend (ISSUE 36)
        "prefill_attended_pairs",
        # ... those of the window layers, and the router's choices beside
        # those a replica's held experts computed (ISSUE 41)
        "prefill_window_pairs", "route_picks", "held_picks",
        "route_picks_decode", "held_picks_decode",
        # the block iteration and the routed experts (ISSUE 30)
        "block_steps", "block_slot_steps", "commit_slot_steps",
        "tokens_unmasked", "block_context_tokens",
        "experts_read", "expert_assignments", "expert_assignments_max",
        "experts_read_decode", "expert_assignments_decode",
        "window_pages_released", "decode_window_tokens",
        "window_pages_live", "window_seq_steps",
        # what the fetches bring to the host (ISSUE 35)
        "fetched_bytes",
        # the step's account (ISSUE 38)
        "step_wall_ns", "step_cpu_ns", "step_nivcsw",
        "step_minflt", "programs_dispatched", "blocking_reads",
        "programs_behind_reads", "slow_steps", "slow_step_excess_ns",
        # the greedy lane's step ahead (ISSUE 39)
        "decode_steps_ahead", "ahead_drains",
    } | {f"step_ns.{phase}" for phase in (
        "admit", "prefill_dispatch", "first_token", "decode_prepare",
        "decode_dispatch", "decode_fetch", "sample_emit", "pool_sync")}
    _CONTRACT_GAUGES = {
        "batch_occupancy", "batch_occupancy_avg",
        "cache_utilization", "cache_utilization_avg",
        "prefix_cached_token_ratio", "degradation_level",
        "health_state", "spec_accept_rate", "stream_active",
        "serving_kv_cache_dtype", "kv_quant_scale_bytes"}

    def _run_workload(self):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import Engine, ServingConfig

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        model.eval()
        eng = Engine(model, ServingConfig(max_batch_size=2, block_size=8,
                                          num_blocks=32))
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 256, size=(n,)).astype(np.int32)
                   for n in (3, 5, 8)]
        eng.generate(prompts, max_new_tokens=4)
        return eng

    def test_as_dict_schema_byte_compatible(self):
        """README "Serving" schema is a contract: the registry mirror
        must not change as_dict()'s shape — enabled or not."""
        obs.enable(True)
        d = self._run_workload().stats()
        assert set(d["counters"]) == self._CONTRACT_COUNTERS
        assert set(d["gauges"]) == self._CONTRACT_GAUGES
        for rid, t in d["requests"].items():
            assert set(t) == {"ttft_s", "tpot_s", "queue_time_s", "e2e_s",
                              "tokens_generated", "preemptions",
                              "finish_reason"}

    def test_mirror_matches_local_counters(self):
        obs.enable(True)
        eng = self._run_workload()
        reg = obs.get_registry()
        c = eng.stats()["counters"]
        assert reg.counter("serving_requests_submitted_total").value() \
            == c["requests_submitted"] == 3
        assert reg.counter("serving_tokens_generated_total").value() \
            == c["tokens_generated"]
        assert reg.counter("serving_decode_iterations_total").value() \
            == c["decode_iterations"]
        assert reg.counter("serving_prefills_total").value() \
            == c["prefills"]
        assert reg.counter("serving_requests_completed_total").value(
            reason="length") == c["requests_completed"]
        # latency histograms observed once per request
        assert reg.get("serving_ttft_seconds").count() == 3
        assert reg.get("serving_queue_seconds").count() == 3
        assert reg.get("serving_e2e_seconds").count() == 3
        assert reg.get("serving_tpot_seconds").count() == 3
        assert 0 < reg.gauge("serving_batch_occupancy").value() <= 1.0

    def test_no_registry_writes_when_disabled(self):
        self._run_workload()
        assert obs.get_registry().names() == []


# ---------------------------------------------------------------------------
# resilience mirror
# ---------------------------------------------------------------------------

class TestCheckpointMetrics:
    def test_save_latency_and_counter(self, tmp_path):
        from paddle_tpu.resilience import ResilientCheckpointer

        obs.enable(True)
        ck = ResilientCheckpointer(str(tmp_path), max_to_keep=5)
        state = {"model": {"w": np.arange(8.0)}}
        ck.save(1, state)
        ck.save(2, state)
        reg = obs.get_registry()
        assert reg.counter("checkpoint_saves_total").value() == 2
        hist = reg.get("checkpoint_save_seconds")
        assert hist.count() == 2
        assert hist.sum() > 0

    def test_corrupt_skipped_counter(self, tmp_path):
        from paddle_tpu.resilience import ResilientCheckpointer

        obs.enable(True)
        ck = ResilientCheckpointer(str(tmp_path))
        state = {"model": {"w": np.arange(4.0)}}
        ck.save(1, state)
        ck.save(2, state)
        # rot the newest checkpoint's payload
        victim = os.path.join(str(tmp_path), "step_00000002", "model.pkl")
        with open(victim, "r+b") as f:
            f.write(b"rotrotrot")
        step, restored = ck.restore_latest()
        assert step == 1 and restored is not None
        assert ck.corrupt_skipped == 1
        assert obs.get_registry().counter(
            "checkpoint_corrupt_skipped_total").value() == 1

    def test_disabled_costs_nothing(self, tmp_path):
        from paddle_tpu.resilience import ResilientCheckpointer

        ck = ResilientCheckpointer(str(tmp_path))
        ck.save(1, {"model": {"w": np.zeros(2)}})
        assert obs.get_registry().names() == []


# ---------------------------------------------------------------------------
# profiler host-tracer fallback
# ---------------------------------------------------------------------------

class TestHostTracerFallback:
    @pytest.fixture()
    def fallback(self, monkeypatch):
        """Force the native load to fail so the pure-Python recorder
        takes over, with module state restored afterwards."""
        from paddle_tpu.profiler import host_tracer as ht

        monkeypatch.setattr(ht, "_lib", None)
        monkeypatch.setattr(ht, "_lib_failed", True)
        monkeypatch.setattr(ht, "_py_recorder", None)
        monkeypatch.setattr(ht, "_intern_cache", {})
        return ht

    def test_begin_end_gated_emit_unconditional(self, fallback):
        ht = fallback
        assert ht.available() is False
        # begin/end before enable: dropped (native ht_begin semantics)
        ht.begin("dropped")
        ht.end()
        # emit records regardless of the enable flag (native ht_emit)
        ht.emit("emitted", 10, 20)
        ht.enable(True)
        ht.begin("ranged")
        ht.end()
        ht.enable(False)
        events = ht.drain()
        names = [e[1] for e in events]
        assert names == ["emitted", "ranged"]
        tid, _, s, e, cat = events[1]
        assert e >= s and cat == "host" and tid > 0
        assert ht.drain() == []                # drained buffers cleared
        assert ht.fallback_active() is True

    def test_intern_cache_cleared_on_fallback(self, fallback):
        ht = fallback
        # poison the cache as if a half-alive native attempt interned ids
        ht._intern_cache["stale"] = 99
        nid = ht.intern("fresh")               # first use builds fallback
        assert "stale" not in ht._intern_cache  # cleared for consistency
        assert ht.intern("fresh") == nid        # stable ids afterwards

    def test_profiler_drains_fallback_events(self, fallback, monkeypatch):
        from paddle_tpu import profiler

        ht = fallback
        rec = profiler._HostEventRecorder()
        monkeypatch.setattr(profiler, "_recorder", rec)
        ht.enable(True)
        ht.begin("direct_range")
        ht.end()
        ht.enable(False)
        rec.record("python_side", 1, 2, category="custom")
        drained = rec.drain()
        by_name = {e[1] for e in drained}
        assert {"direct_range", "python_side"} <= by_name
