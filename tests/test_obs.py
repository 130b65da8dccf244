"""Observability suite: unscoped no-op bit-identity for the instrumented
engine and train loop, span nesting/exception safety, the jax-aware
compile-vs-execute timer split, deterministic event ordering, the
trace-cache-miss (plan-hash churn) detector, JSONL schema round-trips, and
a chaos-sweep reconciliation proving the lifecycle event stream exactly
accounts for every injected fault's retry/degradation/quarantine/failure."""
import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.exec.plan import preset
from repro.obs import (
    REQUEST_PHASES,
    TERMINAL_PHASES,
    Tracer,
    aggregate,
    current_tracer,
    quantiles,
    read_jsonl,
    reconcile,
    render_report,
    use_tracer,
    validate_bench,
    validate_events,
)
from repro.obs import trace as obs
from repro.resilience import InjectedFault, RetryPolicy, inject_faults
from repro.train.loop import instrument_train_step, make_train_step

from test_resilience import (  # noqa: F401  (setup is a fixture)
    _random_specs,
    make_prompts,
    run_engine,
    setup,
)


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------


def test_unscoped_hooks_are_noops(monkeypatch):
    # Unscoped, no hook reaches the profiler: a TraceAnnotation that raises
    # is never built.
    def no_annotation(name):
        raise AssertionError(f"profiler annotation {name!r} without a tracer")

    monkeypatch.setattr(obs, "_annotation", no_annotation)
    assert current_tracer() is None
    with obs.span("free"):                       # null context, no tracer
        pass
    obs.emit("gauge", "nobody")
    obs.count("nothing")
    obs.gauge("nothing", 1)
    assert obs.timed_call("direct", lambda x: x + 1, 41) == 42
    assert current_tracer() is None


def test_scoped_spans_land_on_the_profiler_host_plane(tmp_path):
    """With a tracer scoped, ``span`` and ``timed_call`` also appear on the
    profile's ``/host:CPU`` plane, on the profiler's clock, and the span
    holds the timed call it encloses."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with use_tracer() as tr:
            with obs.span("obs_test.outer"):
                obs.timed_call("obs_test.call", jnp.add, jnp.ones(4), 1.0)
    finally:
        jax.profiler.stop_trace()
    assert [e["name"] for e in tr.events] == ["obs_test.call",
                                              "obs_test.outer"]
    (path,) = tmp_path.glob("**/*.xplane.pb")
    host = [p for p in ProfileData.from_file(str(path)).planes
            if p.name == "/host:CPU"]
    spans = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
             for p in host for line in p.lines for e in line.events
             if e.name.startswith("obs_test.")}
    assert set(spans) == {"obs_test.outer", "obs_test.call"}
    outer, call = spans["obs_test.outer"], spans["obs_test.call"]
    assert outer[0] <= call[0] and call[1] <= outer[1]


def test_use_tracer_scoping_nested_and_exception_safe():
    with use_tracer() as outer:
        assert current_tracer() is outer
        inner_tr = Tracer()
        with use_tracer(inner_tr):
            assert current_tracer() is inner_tr
        assert current_tracer() is outer
        with pytest.raises(RuntimeError, match="boom"):
            with use_tracer():
                raise RuntimeError("boom")
        assert current_tracer() is outer         # restored despite the raise
    assert current_tracer() is None
    with pytest.raises(TypeError):
        with use_tracer("not a tracer"):
            pass


def test_span_nesting_parent_ids_and_error_status():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("mid"):
            with tr.span("leaf"):
                pass
        with pytest.raises(ValueError, match="bad"):
            with tr.span("broken"):
                raise ValueError("bad")
        with tr.span("after"):                   # stack restored post-raise
            pass
    spans = {e["name"]: e for e in tr.events if e["kind"] == "span"}
    assert spans["outer"]["parent_id"] is None
    assert spans["mid"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["leaf"]["parent_id"] == spans["mid"]["span_id"]
    assert spans["broken"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["after"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["broken"]["status"] == "error"
    assert spans["after"]["status"] == "ok"
    # children close before parents, and every span's interval nests inside
    # its parent's
    assert tr.events[-1]["name"] == "outer"
    for name in ("mid", "leaf", "broken", "after"):
        ev, parent = spans[name], spans[
            "outer" if name != "leaf" else "mid"]
        assert ev["t_start_ns"] >= parent["t_start_ns"]
        assert ev["t_start_ns"] + ev["dur_ns"] <= \
            parent["t_start_ns"] + parent["dur_ns"]
    assert not validate_events(tr.events)


def test_counters_accumulate_and_gauges_record():
    tr = Tracer()
    tr.count("tokens")
    tr.count("tokens", 2.0)
    tr.gauge("depth", 7, step=1)
    assert tr.counters == {"tokens": 3.0}
    counter_events = [e for e in tr.events if e["kind"] == "counter"]
    assert [e["value"] for e in counter_events] == [1.0, 3.0]
    (g,) = [e for e in tr.events if e["kind"] == "gauge"]
    assert g["value"] == 7 and g["attrs"]["step"] == 1


def test_timed_call_separates_compile_from_execute():
    tr = Tracer()

    @jax.jit
    def f(x):
        return (x * 2.0).sum()

    x = jnp.ones((256, 256))
    tr.timed_call("f", f, x)                     # cold: trace + compile
    tr.timed_call("f", f, x)                     # warm: enqueue only
    cold, warm = [e for e in tr.events if e["kind"] == "span"]
    for ev in (cold, warm):
        assert ev["attrs"]["dispatch_ns"] >= 0
        assert ev["attrs"]["block_ns"] >= 0
        assert ev["dur_ns"] >= ev["attrs"]["dispatch_ns"]
    # the cold call's host dispatch carries the compile; warm is orders of
    # magnitude cheaper (10x is a very loose bound for a jit compile)
    assert cold["attrs"]["dispatch_ns"] > 10 * warm["attrs"]["dispatch_ns"]


def test_define_interns_values_deterministically():
    tr = Tracer()
    a = preset("default").to_dict()
    b = preset("oracle").to_dict()
    assert tr.define("plan", a) == "plan:0"
    assert tr.define("plan", b) == "plan:1"
    assert tr.define("plan", a) == "plan:0"      # stable on re-intern
    defs = [e for e in tr.events if e["kind"] == "def"]
    assert [d["name"] for d in defs] == ["plan:0", "plan:1"]  # emitted once
    assert defs[0]["value"] == a


def test_jit_entry_counts_plan_hash_churn():
    tr = Tracer()
    assert tr.jit_entry("decode", "plan:0") is True     # expected trace
    assert tr.jit_entry("decode", "plan:0") is False    # hit
    assert tr.jit_entry("decode", "plan:1") is True     # churn!
    assert tr.jit_entry("prefill", "plan:0") is True    # new site: expected
    assert tr.counters.get("trace_cache_miss") == 1.0
    assert [e["cache"] for e in tr.events if e["kind"] == "jit_entry"] == \
        ["miss", "hit", "miss", "miss"]


def test_jsonl_round_trip_resolves_lazy_values(tmp_path):
    tr = Tracer()
    tr.emit("train_step", "train_step", step=1, dur_ns=10, tokens=None,
            metrics={"loss": jnp.float32(1.5)})      # device array: lazy
    path = tmp_path / "events.jsonl"
    assert tr.dump_jsonl(str(path)) == 1
    (ev,) = read_jsonl(str(path))
    assert ev["metrics"]["loss"] == 1.5              # plain float now
    assert not validate_events([ev])
    buf = io.StringIO()
    tr.dump_jsonl(buf)
    assert json.loads(buf.getvalue()) == ev


# ---------------------------------------------------------------------------
# Schema validation
# ---------------------------------------------------------------------------


def test_validator_rejects_malformed_events():
    ok = {"seq": 0, "t_ns": 1, "kind": "gauge", "name": "g", "value": 1,
          "attrs": {}}
    assert not validate_events([ok])
    assert validate_events([{**ok, "kind": "nope"}])        # unknown kind
    assert validate_events([{**ok, "extra": 1}])            # undeclared field
    bad_phase = {"seq": 0, "t_ns": 1, "kind": "request", "name": "vanished",
                 "uid": 1, "attrs": {}}
    assert validate_events([bad_phase])
    missing = dict(ok)
    del missing["value"]
    assert validate_events([missing])
    assert validate_events([ok, ok])                        # seq not increasing


def test_validate_bench_schema():
    row = {"preset": "default", "plan": preset("default").to_dict(),
           "requests": 4, "tokens": 12.0, "wall_s": 1.0,
           "tokens_per_s": 12.0,
           "latency_ms": {"p50": 1.0, "p95": 2.0, "p99": 3.0},
           "occupancy_mean": 2.0, "jit_entries": {}}
    assert not validate_bench({"schema": 1, "rows": [row]})
    assert validate_bench({"schema": 99, "rows": [row]})
    assert validate_bench({"schema": 1, "rows": []})
    assert validate_bench({"schema": 1, "rows": [{**row, "plan": "hash"}]})
    no_lat = {**row, "latency_ms": {"p50": 1.0}}
    assert validate_bench({"schema": 1, "rows": [no_lat]})


def test_quantiles_and_reconcile_units():
    assert quantiles([]) == {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    q = quantiles(list(range(1, 101)))
    assert q["p50"] == 51.0 and q["p95"] == 95.0 and q["p99"] == 99.0

    def req(seq, phase, uid):
        return {"seq": seq, "t_ns": seq, "kind": "request", "name": phase,
                "uid": uid, "attrs": {}}

    good = [req(0, "queued", 1), req(1, "admitted", 1), req(2, "done", 1)]
    assert not reconcile(good)
    assert reconcile([req(0, "queued", 1)])                 # no terminal
    assert reconcile([req(0, "done", 1)])                   # never queued
    double = good + [req(3, "failed", 1)]                   # two terminals
    assert reconcile(double)


# ---------------------------------------------------------------------------
# Engine instrumentation
# ---------------------------------------------------------------------------


def test_unscoped_engine_run_is_bit_identical(setup):
    """The tracer hooks observe, never steer: a traced run produces the
    same tokens and the same final KV cache, bit for bit, as an untraced
    one (the acceptance criterion's no-op guarantee, in the same style as
    the empty-fault-scope test)."""
    cfg, params = setup
    prompts = make_prompts(3)
    eng_a, reqs_a = run_engine(params, cfg, prompts, max_new=3)
    with use_tracer() as tr:
        eng_b, reqs_b = run_engine(params, cfg, prompts, max_new=3)
    assert len(tr.events) > 0
    for a, b in zip(reqs_a, reqs_b):
        assert a.generated == b.generated and b.status == "done"
    for a, b in zip(jax.tree.leaves(eng_a.cache),
                    jax.tree.leaves(eng_b.cache)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_engine_lifecycle_stream_and_report(setup):
    cfg, params = setup
    with use_tracer() as tr:
        run_engine(params, cfg, make_prompts(4), max_new=3)
    events = tr.events_resolved()
    assert not validate_events(events)
    assert not reconcile(events)
    agg = aggregate(events)
    assert agg["requests"]["phases"]["queued"] == 4
    assert agg["requests"]["phases"]["done"] == 4
    assert agg["counters"]["tokens"] == 12.0
    assert agg["meta"]["param_count"] > 0
    assert {"prefill", "decode", "engine.step", "engine.run"} <= \
        set(agg["spans"])
    # self-time: engine.run's own time excludes its engine.step children
    run_span = agg["spans"]["engine.run"]
    assert run_span["self_ns"] < run_span["total_ns"]
    text = render_report(events)
    assert "exactly one terminal state" in text
    # every phase in the stream is a documented one
    assert {e["name"] for e in events if e["kind"] == "request"} <= \
        set(REQUEST_PHASES)


def test_deterministic_event_ordering(setup):
    """Two identical runs produce the same event *sequence* — kind, name,
    uid, and attrs all match position by position (timestamps differ,
    structure must not), and seq is strictly increasing."""
    cfg, params = setup

    def shape(run_events):
        drop = ("t_ns", "t_start_ns", "dur_ns", "dispatch_ns", "block_ns")

        def strip(ev):
            ev = {k: v for k, v in ev.items() if k not in drop}
            if "attrs" in ev:
                ev["attrs"] = {k: v for k, v in ev["attrs"].items()
                               if k not in drop}
            return ev

        return [strip(e) for e in run_events]

    streams = []
    for _ in range(2):
        with use_tracer() as tr:
            run_engine(params, cfg, make_prompts(3), max_new=3)
        streams.append(tr.events_resolved())
    assert shape(streams[0]) == shape(streams[1])
    seqs = [e["seq"] for e in streams[0]]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


def test_mixed_plan_traffic_trips_churn_detector(setup):
    """Two distinct plans in one engine are *expected* to produce two jit
    entries per site — the detector reports exactly the churn beyond the
    first key, which an all-default engine never shows."""
    cfg, params = setup
    prompts = make_prompts(2)
    with use_tracer() as tr:
        run_engine(params, cfg, prompts, max_new=3,
                   plans=[None, preset("oracle")])
    agg = aggregate(tr.events_resolved())
    assert agg["jit"]["decode"]["distinct_keys"] == 2
    assert agg["counters"]["trace_cache_miss"] >= 1.0
    with use_tracer() as tr2:
        run_engine(params, cfg, prompts, max_new=3)
    agg2 = aggregate(tr2.events_resolved())
    assert agg2["jit"]["decode"]["distinct_keys"] == 1
    assert "trace_cache_miss" not in agg2["counters"]


def test_rejected_submit_emits_typed_event(setup):
    cfg, params = setup
    from repro.resilience import AdmissionError
    from repro.serving.engine import ServingEngine

    with use_tracer() as tr:
        eng = ServingEngine(params, cfg, n_slots=2, max_seq=8)
        with pytest.raises(AdmissionError):
            eng.submit(np.zeros((64,), np.int32))
    (ev,) = [e for e in tr.events if e["kind"] == "request"]
    assert ev["name"] == "rejected" and ev["uid"] is None
    assert ev["attrs"]["reason"] == "over_length"
    assert not reconcile(tr.events_resolved())   # uid-less reject is legal


def test_chaos_sweep_event_stream_reconciles(setup):
    """The acceptance criterion's reconciliation proof: under randomized
    injected-fault schedules, the lifecycle event stream accounts for
    every request (exactly one terminal phase matching Request.status) and
    every fired fault maps to a retried/degraded/quarantined/failed event
    for its target uid."""
    cfg, params = setup
    prompts = make_prompts(4, seed=99)
    plans = [None, preset("oracle"), None, preset("oracle")]
    pol = RetryPolicy(max_attempts=3, backoff=1.0,
                      retryable=lambda e: isinstance(e, InjectedFault))
    fired_total = 0
    for seed in range(8):
        rng = np.random.default_rng(seed)
        with use_tracer() as tr:
            with inject_faults(*_random_specs(rng), seed=seed) as inj:
                eng, reqs = run_engine(params, cfg, prompts, max_new=3,
                                       plans=plans, retry=pol)
        fired_total += inj.total_fired
        events = tr.events_resolved()
        assert not validate_events(events), seed
        assert not reconcile(events), seed
        # exactly one terminal event per uid, and it matches the Request
        terminal = {}
        for ev in events:
            if ev["kind"] == "request" and ev["name"] in TERMINAL_PHASES:
                assert ev["uid"] not in terminal, seed
                terminal[ev["uid"]] = ev["name"]
        assert terminal == {r.uid: r.status for r in reqs}, seed
        # every fired fault shows up in its uid's event stream as a retry,
        # degradation, quarantine, or failure
        routed = {p: {e["uid"] for e in events
                      if e["kind"] == "request" and e["name"] == p}
                  for p in ("retried", "degraded", "quarantined", "failed")}
        for fault in inj.events:
            assert any(fault.uid in routed[p] for p in routed), (seed, fault)
    assert fired_total > 0


# ---------------------------------------------------------------------------
# Train-loop instrumentation
# ---------------------------------------------------------------------------


def _toy_setup(guard=True):
    def loss_fn(params, batch, rng):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    init_state, train_step = make_train_step(
        loss_fn, base_lr=1e-2, warmup_steps=2, total_steps=10,
        guard_nonfinite=guard)
    params = {"w": jnp.ones((4, 2), jnp.float32)}
    rng = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
             "y": jnp.asarray(rng.normal(size=(8, 2)), jnp.float32)}
    return init_state(params), train_step, batch


def test_metrics_key_contract_is_never_ragged():
    for guard in (True, False):
        state, step, batch = _toy_setup(guard=guard)
        _, metrics = step(state, batch)
        assert {"loss", "grad_norm", "lr", "nonfinite_skips"} <= set(metrics)
        assert float(metrics["nonfinite_skips"]) == 0.0


def test_unscoped_instrumented_train_step_is_bit_identical():
    state_a, step, batch = _toy_setup()
    state_b = state_a
    jstep = jax.jit(step)
    istep = instrument_train_step(jstep, tokens_per_step=8)
    assert current_tracer() is None
    for _ in range(3):
        state_a, ma = jstep(state_a, batch)
        state_b, mb = istep(state_b, batch)
    np.testing.assert_array_equal(np.asarray(state_a.params["w"]),
                                  np.asarray(state_b.params["w"]))
    assert float(ma["loss"]) == float(mb["loss"])


def test_instrumented_train_step_emits_schema_valid_events():
    state, step, batch = _toy_setup()
    istep = instrument_train_step(jax.jit(step), tokens_per_step=8)
    with use_tracer() as tr:
        for _ in range(4):
            state, _ = istep(state, batch)
    events = tr.events_resolved()
    assert not validate_events(events)
    steps = [e for e in events if e["kind"] == "train_step"]
    assert [e["step"] for e in steps] == [1, 2, 3, 4]
    for ev in steps:
        assert ev["tokens"] == 8
        assert isinstance(ev["metrics"]["loss"], float)
        assert ev["metrics"]["nonfinite_skips"] == 0.0
        assert "lr" not in ev["metrics"]        # only the selected keys ride
    agg = aggregate(events)
    assert agg["train"]["steps"] == 4
    assert agg["train"]["nonfinite_skips"] == 0.0
    assert agg["train"]["tokens"] == 32.0
    assert "train: 4 steps" in render_report(events)
