"""Unified telemetry subsystem (repro.obs): metrics, spans, reports.

Covers the three guarantees the subsystem sells:

* correctness of the shared fold — counters sum, every ``*_rate`` is
  recomputed from the summed counters (never summed or averaged), and
  non-numeric keys survive the merge (the ``session.nested`` regression);
* near-zero disabled cost — ``span()`` with no active tracer returns a
  shared no-op, and the manual ``active()`` guard stays off the store's
  accounting path entirely;
* observational-only tracing — a traced sweep produces bitwise-identical
  search trajectories and store bytes to an untraced one, while the
  recorded ``simulate_batch`` spans sum exactly to the engine's evaluation
  counters.
"""

import dataclasses
import json
import time

import pytest

from repro.core import nas, proxy, scenarios, sweep
from repro.core.search import SearchConfig
from repro.core.session import SearchSession
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry, merge_stats, rate

SC = scenarios.get("lat-0.3ms")
CFG = SearchConfig(samples=24, batch=8, controller="evolution")


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing disabled — a test that
    starts a tracer or the profiler sink must not leak it into the rest of
    the suite."""
    obs_trace.stop()
    obs_trace.annotate(False)
    yield
    obs_trace.stop()
    obs_trace.annotate(False)


# ---------------------------------------------------------------------------
# rate + merge_stats (the one shared fold)
# ---------------------------------------------------------------------------


def test_rate_guards_zero_denominator():
    assert rate(0, 0) == 0.0
    assert rate(3, 0) == 3.0  # max(den, 1)
    assert rate(1, 4) == 0.25


def test_merge_sums_counters_and_recomputes_rates():
    merged = merge_stats(
        [
            {"gets": 10, "hits": 9, "cross_hits": 0, "hit_rate": 0.9},
            {"gets": 90, "hits": 1, "cross_hits": 1, "hit_rate": 1 / 90},
        ]
    )
    assert merged["gets"] == 100 and merged["hits"] == 10
    # recomputed from summed counters: 10/100, NOT mean(0.9, 0.011) = 0.456
    assert merged["hit_rate"] == pytest.approx(0.1)
    assert merged["cross_hit_rate"] == pytest.approx(0.01)


def test_merge_engine_shaped_hit_rate_uses_second_candidate():
    # engine dicts expose hit_rate over cache_hits/requested, not hits/gets
    merged = merge_stats(
        [
            {"requested": 8, "cache_hits": 2, "hit_rate": 0.25},
            {"requested": 8, "cache_hits": 6, "hit_rate": 0.75},
        ]
    )
    assert merged["hit_rate"] == pytest.approx(0.5)


def test_merge_passes_non_numeric_through():
    merged = merge_stats([{"puts": 1, "label": "a"}, {"puts": 2, "label": "a"}])
    assert merged["label"] == "a"  # single distinct value stays scalar
    two = merge_stats([{"label": "a"}, {"label": "b"}])
    assert two["label"] == ["'a'", "'b'"]  # disagreement: sorted reprs


def test_merge_defaults_stabilize_empty_schema():
    merged = merge_stats([], defaults={"gets": 0, "hits": 0})
    assert merged == {"gets": 0, "hits": 0, "hit_rate": 0.0}


def test_merge_counts_bools():
    merged = merge_stats([{"ok": True}, {"ok": True}, {"ok": False}])
    assert merged["ok"] == 2


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_export_and_weak_registration():
    reg = MetricsRegistry()

    @dataclasses.dataclass
    class S:
        gets: int = 0
        hits: int = 0

        def as_dict(self):
            return {"gets": self.gets, "hits": self.hits}

    a, b = S(gets=10, hits=5), S(gets=30, hits=3)
    reg.register("store", a)
    reg.register("store", b)
    out = reg.export()
    assert out["stats"]["store"]["gets"] == 40
    assert out["stats"]["store"]["instances"] == 2
    del b  # dead object drops out of the next export
    assert reg.export()["stats"]["store"]["gets"] == 10


def test_repo_stats_objects_self_register():
    from repro.core.engine import EngineStats

    before = obs_metrics.REGISTRY.export()["stats"].get("engine", {})
    st = EngineStats(requested=7, cache_hits=2)
    after = obs_metrics.REGISTRY.export()["stats"]["engine"]
    assert after["requested"] == before.get("requested", 0) + 7
    del st


# ---------------------------------------------------------------------------
# session.nested regression (satellite: stats fold through merge_stats)
# ---------------------------------------------------------------------------


def test_nested_session_stats_fold_is_consistent():
    res = SearchSession(
        nas.tiny_space(), proxy.SurrogateAccuracy(), cfg=CFG
    ).nested(scenario=SC, outer=2)
    st = res.engine_stats
    assert st["requested"] > 0
    # the folded hit_rate is the rate over SUMMED counters, not an average
    assert st["hit_rate"] == pytest.approx(rate(st["cache_hits"], st["requested"]))
    assert st["evaluated"] + st["cache_hits"] == st["requested"]


# ---------------------------------------------------------------------------
# disabled cost
# ---------------------------------------------------------------------------


def test_span_disabled_returns_shared_noop():
    assert obs_trace.active() is None
    s1 = obs_trace.span("x", n=1)
    s2 = obs_trace.span("y")
    assert s1 is s2 is obs_trace._NOOP
    with s1 as sp:
        assert sp.set(k=2) is sp  # chainable no-op


def test_span_disabled_is_cheap():
    """The no-op guard budget: a disabled span() must stay far below µs
    scale (the ISSUE budget is ns; the bound here is lenient for CI
    noise, catching only an accidentally-expensive guard)."""
    n = 200_000
    t0 = time.perf_counter_ns()
    for _ in range(n):
        with obs_trace.span("x"):
            pass
    per_call_ns = (time.perf_counter_ns() - t0) / n
    assert per_call_ns < 2_000, f"disabled span cost {per_call_ns:.0f}ns/op"


def test_span_off_again_after_the_profiler_sink():
    obs_trace.annotate(True)
    assert obs_trace.span("x") is not obs_trace._NOOP
    assert obs_trace.active() is None  # the JSONL tracer stays off
    obs_trace.annotate(False)
    assert obs_trace.span("x") is obs_trace._NOOP


def test_profiler_sink_puts_spans_in_the_device_profile(tmp_path):
    """With annotate on, a span is a TraceAnnotation: it shows up on the
    host plane of a jax.profiler trace, beside the JSONL record."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(4)
    f(x).block_until_ready()
    obs_trace.start(tmp_path / "jsonl")
    obs_trace.annotate(True)
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        with obs_trace.span("repro.test.span", n=1):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    obs_trace.stop()
    pb = sorted((tmp_path / "prof").rglob("*.xplane.pb"))[-1]
    host = [
        ev.name
        for plane in ProfileData.from_file(str(pb)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    ]
    assert "repro.test.span" in host
    lines = (tmp_path / "jsonl" / "trace.jsonl").read_text().splitlines()
    assert any(json.loads(ln).get("name") == "repro.test.span" for ln in lines)


def test_traced_training_loop_records_its_spans(tmp_path):
    """A traced run_training writes the data and loop spans to the JSONL
    file: one of each per step, one per checkpoint."""
    import jax.numpy as jnp

    from repro.data.synthetic import LMStream
    from repro.train.loop import LoopConfig, run_training

    stream = LMStream(16, 8, 2, seed=0)

    def step_fn(state, batch):
        x = state["x"] + jnp.asarray(batch["tokens"]).sum()
        return {"x": x}, {"loss": x}

    obs_trace.start(tmp_path / "trace")
    run_training(step_fn, {"x": jnp.zeros(())}, stream.batch_at,
                 LoopConfig(total_steps=4, ckpt_every=2, ckpt_dir=str(tmp_path / "ck"),
                            log_every=100, async_ckpt=False),
                 log_fn=lambda s: None)
    obs_trace.stop()
    lines = (tmp_path / "trace" / "trace.jsonl").read_text().splitlines()
    names = [e["name"] for e in map(json.loads, lines) if e.get("ph") == "X"]
    for name in ("repro.data.batch", "repro.train.batch", "repro.train.step",
                 "repro.train.wait"):
        assert names.count(name) == 4
    assert names.count("repro.train.checkpoint") == 3  # steps 2 and 4, the final save


def test_train_launcher_trace_and_profile(tmp_path, monkeypatch, capsys):
    """``launch/train.py --trace DIR --profile DIR``: the loop's spans in the
    JSONL file, and on the host plane of the run's device profile."""
    import sys

    from jax.profiler import ProfileData

    from repro.launch import train as launch_train

    monkeypatch.setattr(launch_train, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "train", "--arch", "mamba2-370m", "--smoke", "--steps", "2", "--seq", "16",
        "--batch", "2", "--ckpt", str(tmp_path / "ck"),
        "--trace", str(tmp_path / "trace"), "--profile", str(tmp_path / "prof")])
    launch_train.main()
    assert "done: 2 steps" in capsys.readouterr().out
    assert obs_trace.span("x") is obs_trace._NOOP  # both sinks off again
    lines = (tmp_path / "trace" / "trace.jsonl").read_text().splitlines()
    names = {e["name"] for e in map(json.loads, lines) if e.get("ph") == "X"}
    want = {"repro.data.batch", "repro.train.batch", "repro.train.step",
            "repro.train.wait", "repro.train.checkpoint"}
    assert want <= names
    assert (tmp_path / "trace" / "metrics.json").exists()
    pb = sorted((tmp_path / "prof").rglob("*.xplane.pb"))[-1]
    host = {
        ev.name
        for plane in ProfileData.from_file(str(pb)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    }
    assert want <= host


def test_store_namespace_accounting_off_without_tracer():
    from repro.core.engine import RecordStore

    store = RecordStore()
    store.put(b"n" * 24, {"valid": True}, writer="w")
    store.get(b"n" * 24)
    assert store.namespace_stats() == {}


# ---------------------------------------------------------------------------
# trace record -> merge -> validate
# ---------------------------------------------------------------------------


def _write_two_segment_trace(d):
    obs_trace.start(d)
    with obs_trace.span("simulate_batch", n=8, label="lat-0.3ms"):
        pass
    with obs_trace.span("job", job="sweep.lat-0.3ms") as sp:
        sp.set(status="done")
    obs_trace.stop()
    obs_trace.start(d, worker=1)
    with obs_trace.span("simulate_batch", n=4, label="lat-0.8ms"):
        pass
    obs_trace.stop()


def test_trace_merge_validate_roundtrip(tmp_path):
    _write_two_segment_trace(tmp_path)
    assert [p.name for p in obs_trace.trace_paths(tmp_path)] == [
        "trace.jsonl",
        "trace.jsonl.worker-1",
    ]
    merged = obs_trace.merge(tmp_path)
    info = obs_report.validate_chrome_trace(merged)
    assert info["tracks"] == 2  # one per source file
    assert {"simulate_batch", "job"} <= set(info["names"])
    payload = json.loads(merged.read_text())
    # per-file labeled tracks, the thing Perfetto renders
    procs = {
        ev["args"]["name"]
        for ev in payload["traceEvents"]
        if ev.get("ph") == "M" and ev["name"] == "process_name"
    }
    assert procs == {"main", "worker-1"}
    # span args survive the merge (including set() overrides)
    jobs = [ev for ev in payload["traceEvents"] if ev.get("name") == "job"]
    assert jobs[0]["args"] == {"job": "sweep.lat-0.3ms", "status": "done"}


def test_merge_tolerates_torn_segment_tail(tmp_path):
    _write_two_segment_trace(tmp_path)
    with open(tmp_path / "trace.jsonl.worker-1", "a") as f:
        f.write('{"name": "torn')  # killed writer mid-append
    merged = obs_trace.merge(tmp_path)
    info = obs_report.validate_chrome_trace(merged)
    assert info["spans"] == 3  # torn line dropped, everything else kept


def test_validator_rejects_broken_traces(tmp_path):
    bad = tmp_path / "t.json"
    bad.write_text(json.dumps({"traceEvents": []}))
    with pytest.raises(ValueError, match="missing or empty"):
        obs_report.validate_chrome_trace(bad)
    unsorted_events = [
        {"name": "a", "ph": "X", "ts": 5.0, "dur": 1, "pid": 0, "tid": 0},
        {"name": "b", "ph": "X", "ts": 2.0, "dur": 1, "pid": 0, "tid": 0},
    ]
    bad.write_text(json.dumps({"traceEvents": unsorted_events}))
    with pytest.raises(ValueError, match="precedes"):
        obs_report.validate_chrome_trace(bad)
    no_tid = [{"name": "a", "ph": "X", "ts": 1.0, "pid": 0}]
    bad.write_text(json.dumps({"traceEvents": no_tid}))
    with pytest.raises(ValueError, match="missing 'tid'"):
        obs_report.validate_chrome_trace(bad)


def test_report_build_and_render(tmp_path):
    _write_two_segment_trace(tmp_path)
    obs_report.write_metrics(
        tmp_path,
        extra={"namespaces": {"abcd": {"gets": 4, "hits": 2, "hit_rate": 0.5}}},
    )
    rep = obs_report.build_report(tmp_path)
    assert rep["spans"]["simulate_batch"]["count"] == 2
    assert rep["scenarios"]["lat-0.3ms"]["evaluations"] == 8
    assert rep["scenarios"]["lat-0.8ms"]["evaluations"] == 4
    assert len(rep["workers"]) == 2
    text = obs_report.render_report(rep)
    assert "simulate_batch" in text and "worker-1" in text
    assert "hit_rate=50.0%" in text


# ---------------------------------------------------------------------------
# tracing is observational only (the hard guarantee)
# ---------------------------------------------------------------------------


def _run_sweep(tmp_path, name, trace_dir=None):
    from repro.runtime import DurableRecordStore

    if trace_dir is not None:
        obs_trace.start(trace_dir)
    try:
        cfg = sweep.SweepConfig(
            search=dataclasses.replace(CFG, store=DurableRecordStore(tmp_path / name))
        )
        runner = sweep.SweepRunner(
            ["lat-0.3ms", "edge-sku-nano"],
            nas.tiny_space(),
            proxy.SurrogateAccuracy(),
            cfg,
        )
        result = runner.run()
        cfg.search.store.close()
        return result
    finally:
        if trace_dir is not None:
            obs_trace.stop()


def test_traced_sweep_identical_results_and_store_bytes(tmp_path):
    plain = _run_sweep(tmp_path, "plain.jsonl")
    traced = _run_sweep(tmp_path, "traced.jsonl", trace_dir=tmp_path / "tr")

    for po, to in zip(plain.outcomes, traced.outcomes):
        assert to.result.history == po.result.history  # bitwise
        assert to.best == po.best
    assert traced.frontier.records() == plain.frontier.records()
    # the durable log is byte-identical: tracing never touches store bytes
    traced_bytes = (tmp_path / "traced.jsonl").read_bytes()
    assert traced_bytes == (tmp_path / "plain.jsonl").read_bytes()


def test_simulate_batch_spans_sum_to_engine_evaluations(tmp_path):
    traced = _run_sweep(tmp_path, "t.jsonl", trace_dir=tmp_path / "tr")
    evaluated = sum(o.result.engine_stats["evaluated"] for o in traced.outcomes)
    span_n = 0
    with open(tmp_path / "tr" / "trace.jsonl") as f:
        for line in f:
            ev = json.loads(line)
            if ev.get("name") == "simulate_batch":
                span_n += ev["args"]["n"]
    assert span_n == evaluated > 0


def test_namespace_stats_recorded_under_tracer(tmp_path):
    traced = _run_sweep(tmp_path, "t.jsonl", trace_dir=tmp_path / "tr")
    assert traced is not None
    # the store was built under an active tracer, so per-namespace gets/hits
    # were accounted; both scenarios share one (space, signal) namespace
    from repro.runtime import DurableRecordStore

    obs_trace.start(tmp_path / "tr2")
    try:
        store = DurableRecordStore(tmp_path / "t.jsonl", read_only=True)
        cfg = sweep.SweepConfig(search=dataclasses.replace(CFG, store=store))
        sweep.SweepRunner(
            ["lat-0.3ms"], nas.tiny_space(), proxy.SurrogateAccuracy(), cfg
        ).run()
        ns = store.namespace_stats()
    finally:
        obs_trace.stop()
    assert len(ns) == 1
    [(_digest, d)] = ns.items()
    assert d["gets"] > 0 and d["hit_rate"] == rate(d["hits"], d["gets"])
