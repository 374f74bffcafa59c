"""Observability-layer tests (obs/ — ISSUE 11).

Covers the three sinks and their wiring contracts:

- Tracer: cross-thread span recording, proper nesting per thread track
  (validate_nesting both accepting real traces and flagging synthetic
  partial overlaps), async request-flow events, Chrome-trace export.
- EventJournal: per-process sequence ids, deterministic (proc, seq)
  multi-host merge, the serve-lifecycle conservation law — including
  under the seeded chaos workload (poison + expiry from 8 threads
  against the jax-free _StubPool) and under trainer NaN injection.
- MetricsRegistry: Prometheus-text and JSON exposition goldens,
  collector flattening (ServeStats.attach_registry), cross-host merge
  semantics (counters sum, gauges max, histogram binning mismatch
  raises).
- Config gating: ObsConfig.from_env's None sentinel, and from_config
  returning the shared zero-cost NOOP bundle whenever obs is off.
"""

import json
import os
import threading

import numpy as np
import pytest

from parallel_cnn_tpu import obs as obs_lib
from parallel_cnn_tpu.config import ObsConfig
from parallel_cnn_tpu.obs.events import EventJournal, conservation, merge_journals
from parallel_cnn_tpu.obs.registry import MetricsRegistry
from parallel_cnn_tpu.obs.trace import NOOP_TRACER, Tracer, validate_nesting

pytestmark = pytest.mark.obs


# ------------------------------------------------------------------ tracer


def test_span_nesting_valid_across_threads(tmp_path):
    """8 threads of seeded nested spans produce a properly nested trace
    with one thread_name metadata record per thread."""
    tracer = Tracer(process_name="test", mirror_jax=False)
    # All workers rendezvous before spanning: a worker that finished
    # before another started could hand its (recycled) thread ident to
    # it, merging two metadata lanes — the barrier pins 8 live threads.
    barrier = threading.Barrier(8)

    def worker(tid):
        barrier.wait()
        rng = np.random.default_rng((7, tid))
        for i in range(20):
            with tracer.span("outer", cat="t", tid=tid, i=i):
                for _ in range(int(rng.integers(1, 4))):
                    with tracer.span("inner", cat="t"):
                        with tracer.span("leaf", cat="t"):
                            pass

    threads = [
        threading.Thread(target=worker, args=(t,), name=f"obs-{t}")
        for t in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    events = tracer.events()
    assert validate_nesting(events) == []
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) >= 8 * 20 * 3  # outer + >=1 inner + >=1 leaf each
    thread_meta = [
        e for e in events if e["ph"] == "M" and e["name"] == "thread_name"
    ]
    assert len(thread_meta) == 8
    # monotonic-clock timestamps: every span has non-negative duration
    assert all(e["dur"] >= 0 for e in xs)


def test_validate_nesting_flags_partial_overlap():
    good = [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0.0, "dur": 10.0},
        {"ph": "X", "name": "b", "pid": 1, "tid": 1, "ts": 2.0, "dur": 3.0},
        {"ph": "X", "name": "c", "pid": 1, "tid": 1, "ts": 6.0, "dur": 2.0},
    ]
    assert validate_nesting(good) == []
    bad = good + [
        # starts inside 'a' but ends after it: partial overlap
        {"ph": "X", "name": "z", "pid": 1, "tid": 1, "ts": 9.0, "dur": 5.0},
    ]
    problems = validate_nesting(bad)
    assert len(problems) == 1 and "'z'" in problems[0]
    # a different thread is a different track — no interaction
    other = good + [
        {"ph": "X", "name": "z", "pid": 1, "tid": 2, "ts": 9.0, "dur": 5.0},
    ]
    assert validate_nesting(other) == []


def test_tracer_export_is_loadable_chrome_trace(tmp_path):
    tracer = Tracer(process_name="pcnn:test", mirror_jax=False)
    with tracer.span("step", cat="train", epoch=1):
        pass
    tracer.begin_async("request", 0xBEEF)
    tracer.end_async("request", 0xBEEF)
    path = tracer.export(str(tmp_path / "t" / "trace.json"))
    with open(path) as f:
        payload = json.load(f)
    assert payload["displayTimeUnit"] == "ms"
    evs = payload["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert {"M", "X", "b", "e"} <= phases
    proc = [e for e in evs if e["ph"] == "M" and e["name"] == "process_name"]
    assert proc and proc[0]["args"]["name"] == "pcnn:test"
    span = next(e for e in evs if e["ph"] == "X")
    assert span["args"] == {"epoch": 1}
    b = next(e for e in evs if e["ph"] == "b")
    assert b["id"] == "0xbeef" and b["cat"] == "req"


# ----------------------------------------------------------------- journal


def test_journal_seq_ids_and_counts(tmp_path):
    j = EventJournal(str(tmp_path / "j.jsonl"), process_index=3)
    j.emit("epoch", epoch=1, loss=0.5)
    j.emit("epoch", epoch=2, loss=0.4)
    j.emit("checkpoint", epoch=2)
    j.close()
    recs = obs_lib.read_journal(j.path)
    assert [r["seq"] for r in recs] == [1, 2, 3]
    assert all(r["proc"] == 3 for r in recs)
    assert recs[0]["loss"] == 0.5
    assert j.counts() == {"epoch": 2, "checkpoint": 1}


def test_merge_journals_is_deterministic(tmp_path):
    """Merge orders by (proc, seq) regardless of file order or wall
    clock — the skew-proof contract."""
    j0 = EventJournal(str(tmp_path / "h0.jsonl"), process_index=0)
    j1 = EventJournal(str(tmp_path / "h1.jsonl"), process_index=1)
    j1.emit("epoch", epoch=1)  # written first in wall-clock time
    j0.emit("epoch", epoch=1)
    j0.emit("epoch", epoch=2)
    j1.emit("epoch", epoch=2)
    j0.close()
    j1.close()
    a = merge_journals([j0.path, j1.path])
    b = merge_journals([j1.path, j0.path])
    assert a == b
    assert [(r["proc"], r["seq"]) for r in a] == [
        (0, 1), (0, 2), (1, 1), (1, 2),
    ]


def test_conservation_law_direct():
    assert conservation({}) is None  # no submits journaled
    assert conservation({"epoch": 5}) is None
    ok = {"submit": 10, "complete": 7, "shed": 1, "expired": 1, "failed": 1}
    assert conservation(ok) is None
    bad = {"submit": 10, "complete": 7}
    msg = conservation(bad)
    assert msg is not None and "submit=10" in msg


def test_batcher_journal_conservation_under_chaos(tmp_path):
    """The seeded race-harness workload (poison + expiry + shedding from
    8 threads, jax-free _StubPool) keeps the journal's lifecycle counts
    conserved and agreeing with ServeStats — for every interleaving."""
    from parallel_cnn_tpu.analysis.concurrency import _StubPool
    from parallel_cnn_tpu.serve.batcher import DynamicBatcher, Overloaded

    tracer = Tracer(process_name="chaos", mirror_jax=False)
    journal = EventJournal(str(tmp_path / "serve.jsonl"))
    bundle = obs_lib.Obs(
        tracer, MetricsRegistry(), journal, enabled=True,
        trace_path=str(tmp_path / "serve_trace.json"),
    )
    pool = _StubPool(seed=11)
    batcher = DynamicBatcher(
        pool, max_wait_ms=1.0, queue_depth=4, start=True, obs=bundle
    )

    def worker(tid):
        rng = np.random.default_rng((11, tid))
        futures = []
        for i in range(40):
            x = np.full((4,), float(tid * 40 + i), np.float32)
            if rng.uniform() < 0.05:
                x[0] = -1.0  # poison: the whole batch fails
            deadline_ms = 1e-3 if rng.uniform() < 0.1 else None
            try:
                futures.append(batcher.submit(x, deadline_ms=deadline_ms))
            except Overloaded:
                continue
        for fut in futures:
            try:
                fut.result(timeout=30)
            except Exception:
                pass

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    batcher.close()

    jc = journal.counts()
    assert jc.get("submit", 0) == 8 * 40
    assert conservation(jc) is None
    snap = batcher.stats.snapshot()
    for jkind, skey in (
        ("submit", "submitted"), ("complete", "completed"),
        ("shed", "shed"), ("expired", "expired"), ("failed", "failed"),
    ):
        assert jc.get(jkind, 0) == snap[skey], (
            f"journal {jkind}={jc.get(jkind, 0)} disagrees with "
            f"ServeStats {skey}={snap[skey]}"
        )
    assert validate_nesting(tracer.events()) == []


@pytest.mark.chaos
def test_trainer_nan_chaos_writes_journal(tmp_path):
    """NaN injection under the rollback policy leaves a reconstructable
    story in the journal: chaos → verdict(unhealthy) → rollback, then
    the full epoch count once healthy."""
    from parallel_cnn_tpu.config import (
        Config, DataConfig, ResilienceConfig, TrainConfig,
    )
    from parallel_cnn_tpu.data import pipeline
    from parallel_cnn_tpu.resilience.chaos import ChaosMonkey
    from parallel_cnn_tpu.train import trainer

    cfg = Config(
        data=DataConfig(
            loader="synthetic", synthetic_train_count=64,
            synthetic_test_count=16,
        ),
        train=TrainConfig(epochs=2, batch_size=16, shuffle=True),
        resilience=ResilienceConfig(policy="rollback", max_rollbacks=2),
    )
    train_ds, _ = pipeline.load_train_test(cfg.data)
    bundle = obs_lib.from_config(
        ObsConfig(trace=True, dir=str(tmp_path), jax_annotations=False),
        run="t",
    )
    result = trainer.learn(
        cfg, train_ds, verbose=False, chaos=ChaosMonkey(nan_step=1),
        obs=bundle,
    )
    arts = bundle.finish()
    assert result.rollbacks >= 1
    counts = {}
    for rec in obs_lib.read_journal(arts["journal"]):
        counts[rec["kind"]] = counts.get(rec["kind"], 0) + 1
    assert counts.get("chaos", 0) == 1
    assert counts.get("verdict", 0) >= 1
    assert counts.get("rollback", 0) >= 1
    assert counts.get("epoch", 0) == 2
    with open(arts["trace"]) as f:
        evs = json.load(f)["traceEvents"]
    assert validate_nesting(evs) == []
    names = {e["name"] for e in evs if e["ph"] == "X"}
    assert "train.epoch" in names and "train.readback" in names


# ---------------------------------------------------------------- registry


def test_prometheus_text_golden():
    reg = MetricsRegistry()
    reg.counter("train.steps", help="total steps").inc(3)
    reg.gauge("queue.depth").set(2)
    reg.histogram("lat").record(0.5)
    assert reg.prometheus_text() == (
        "# HELP train_steps total steps\n"
        "# TYPE train_steps counter\n"
        "train_steps 3\n"
        "# TYPE queue_depth gauge\n"
        "queue_depth 2.0\n"
        "# TYPE lat summary\n"
        'lat{quantile="0.50"} 0.5\n'
        'lat{quantile="0.90"} 0.5\n'
        'lat{quantile="0.99"} 0.5\n'
        "lat_count 1\n"
        "lat_sum 0.5\n"
    )


def test_json_snapshot_and_collector_flattening(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc(2)
    reg.attach("serve", lambda: {"submitted": 4, "latency_ms": {"count": 2}})
    snap = reg.json_snapshot()
    assert snap["counters"] == {"c": 2}
    assert snap["collected"]["serve"]["submitted"] == 4
    # collectors render as flattened gauges in the Prometheus text
    text = reg.prometheus_text()
    assert "serve_latency_ms_count 2.0" in text
    assert "serve_submitted 4.0" in text
    path = reg.write_json(str(tmp_path / "m" / "metrics.json"))
    with open(path) as f:
        assert json.load(f)["counters"] == {"c": 2}


def test_serve_stats_attach_registry():
    from parallel_cnn_tpu.serve.telemetry import ServeStats

    stats = ServeStats()
    stats.on_submit()
    stats.on_submit()
    stats.on_complete(0.01)
    reg = MetricsRegistry()
    stats.attach_registry(reg)
    snap = reg.json_snapshot()
    assert snap["collected"]["serve"]["submitted"] == 2
    assert snap["collected"]["serve"]["completed"] == 1
    # live, not cached: the next exposition sees new counts
    stats.on_submit()
    assert reg.json_snapshot()["collected"]["serve"]["submitted"] == 3


def test_registry_merge_two_hosts():
    host0, host1 = MetricsRegistry(), MetricsRegistry()
    host0.counter("steps").inc(5)
    host1.counter("steps").inc(7)
    host1.counter("only_h1").inc(1)
    host0.gauge("depth").set(2)
    host1.gauge("depth").set(9)
    host0.histogram("lat").record(0.1)
    host1.histogram("lat").record(0.3)
    host0.merge(host1)
    assert host0.counter("steps").value == 12  # counters sum
    assert host0.counter("only_h1").value == 1
    assert host0.gauge("depth").value == 9.0  # gauges take max
    assert host0.histogram("lat").count == 2  # histograms fold
    # binning mismatch must raise, never silently mis-merge
    a, b = MetricsRegistry(), MetricsRegistry()
    a.histogram("h", lo=1e-5, hi=100.0, bins=96)
    b.histogram("h", lo=1e-3, hi=10.0, bins=32).record(0.5)
    with pytest.raises(ValueError, match="binning mismatch"):
        a.merge(b)


# ------------------------------------------------------------------ gating


def test_obsconfig_from_env_none_sentinel(monkeypatch):
    for var in ("PCNN_OBS_TRACE", "PCNN_OBS_DIR",
                "PCNN_OBS_METRICS_JSON", "PCNN_OBS_JAX"):
        monkeypatch.delenv(var, raising=False)
    assert ObsConfig.from_env() is None

    monkeypatch.setenv("PCNN_OBS_TRACE", "1")
    cfg = ObsConfig.from_env()
    assert cfg is not None and cfg.trace and cfg.enabled
    assert cfg.dir == "obs_out" and cfg.jax_annotations

    monkeypatch.setenv("PCNN_OBS_TRACE", "0")
    cfg = ObsConfig.from_env()
    assert cfg is not None and not cfg.trace and not cfg.enabled

    monkeypatch.setenv("PCNN_OBS_METRICS_JSON", "/tmp/m.json")
    monkeypatch.setenv("PCNN_OBS_DIR", "elsewhere")
    monkeypatch.setenv("PCNN_OBS_JAX", "0")
    cfg = ObsConfig.from_env()
    assert cfg.enabled and not cfg.trace  # metrics-only mode
    assert cfg.metrics_json == "/tmp/m.json"
    assert cfg.dir == "elsewhere" and not cfg.jax_annotations


def test_noop_bundle_retains_no_allocation_over_10k_spans():
    """Off must cost nothing that accumulates: 10,000 span + event calls
    on the disabled bundle leave no allocation behind."""
    import tracemalloc

    noop = obs_lib.from_config(None)
    tracemalloc.start()
    try:
        with noop.span("warm"):
            noop.event("warm")
        before = tracemalloc.get_traced_memory()[0]
        for _ in range(10000):
            with noop.span("hot", step=1):
                noop.event("hot", step=1)
        retained = tracemalloc.get_traced_memory()[0] - before
    finally:
        tracemalloc.stop()
    assert retained < 16384, retained
    assert noop.tracer.events() == []


def test_from_config_gating_and_noop_identity(tmp_path):
    # off both ways → the shared zero-cost singleton
    assert obs_lib.from_config(None) is obs_lib.NOOP
    off = ObsConfig(trace=False)
    assert obs_lib.from_config(off) is obs_lib.NOOP
    # the no-op span is one reusable object: no per-call allocation
    noop = obs_lib.NOOP
    assert noop.span("a") is noop.span("b")
    assert not noop.enabled
    assert noop.event("epoch", epoch=1) is None
    assert noop.finish() == {}
    assert noop.tracer.events() == []

    # metrics-only: live registry, but no tracer/journal/files
    mj = str(tmp_path / "m.json")
    bundle = obs_lib.from_config(
        ObsConfig(trace=False, metrics_json=mj), run="x"
    )
    assert bundle.enabled
    assert bundle.tracer is NOOP_TRACER
    assert not bundle.journal.enabled
    bundle.registry.counter("c").inc()
    arts = bundle.finish()
    assert set(arts) == {"metrics"}
    assert not (tmp_path / "obs_out").exists()

    # trace mode names artifacts by run so phases don't clobber
    full = obs_lib.from_config(
        ObsConfig(trace=True, dir=str(tmp_path), jax_annotations=False),
        run="phase1",
    )
    with full.span("s"):
        pass
    full.event("epoch", epoch=1)
    arts = full.finish()
    assert arts["trace"].endswith("phase1_trace.json")
    assert arts["journal"].endswith("phase1_journal.jsonl")
