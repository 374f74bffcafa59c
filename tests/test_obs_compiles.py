"""The process's compile log (obs/compiles.py), the tracer's open spans it
names `within` from (obs/trace.py:innermost), and `zoo.train`'s set-up as
spans, a `zoo_setup` event and the epoch record's `compiles`.

On the CPU, against a temporary persistent compile cache that keeps every
program (`jax_persistent_cache_min_compile_time_secs` 0, as
`benchmark/run.py` sets it); every `jax.config` value is put back, and
the log is emptied around each test by tests/conftest.py.
"""

import json
import sys
import threading
import time

import jax
import jax.monitoring
import jax.numpy as jnp
import pytest

from parallel_cnn_tpu import obs as obs_lib
from parallel_cnn_tpu.config import ObsConfig
from parallel_cnn_tpu.nn.core import Sequential
from parallel_cnn_tpu.nn.layers import ConvBNAct, Dense, GlobalAvgPool
from parallel_cnn_tpu.obs import compiles, trace as trace_lib
from parallel_cnn_tpu.train import zoo

pytestmark = pytest.mark.obs

COMPILE = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def cache(tmp_path):
    """A persistent compile cache of this test's own, asked for every
    program; the log installed."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    compiles.install()
    compiles.clear()
    try:
        yield tmp_path / "cache"
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _fresh(name):
    """A jitted function nobody has compiled, under a name of its own."""
    def f(x):
        return jnp.sin(x) * 2.0 + jnp.cos(x)  # three jnp wrappers inside
    f.__name__ = f.__qualname__ = name
    return jax.jit(f)


def _of(name):
    """The kept records of the function `name`, by kind."""
    out = {}
    for r in compiles.records():
        if r.fun_name in (name, f"jit({name})"):
            out.setdefault(r.kind, []).append(r)
    return out


def _traced_obs():
    return obs_lib.Obs(obs_lib.Tracer(mirror_jax=False),
                       obs_lib.MetricsRegistry(), obs_lib.NOOP_JOURNAL,
                       enabled=True)


# ------------------------------------------------------------------ the log

def test_a_fresh_function_is_one_trace_one_lowering_one_compile_that_missed(cache):
    f = _fresh("fresh_a")
    x = jnp.ones(3)  # its own small programs come first
    t0 = time.perf_counter()
    jax.block_until_ready(f(x))
    took = time.perf_counter() - t0
    got = _of("fresh_a")
    assert {k: len(v) for k, v in got.items()} == {
        "trace": 1, "lower": 1, "compile": 1}
    # what sin, cos and multiply traced inside it is its own, not theirs
    assert not [r for r in compiles.records() if r.start >= got["trace"][0].start
                and r.fun_name in ("sin", "cos", "multiply")]
    (c,) = got["compile"]
    assert (c.cache, c.load_s, c.within, c.ids) == ("miss", None, None, None)
    assert c.thread == threading.get_ident() and c.thread_name == "MainThread"
    for kind in ("trace", "lower"):
        assert got[kind][0].cache is None
    own = [got[k][0] for k in ("trace", "lower", "compile")]
    assert all(0 < r.seconds <= r.span_s for r in own)
    assert sum(r.seconds for r in own) <= took
    assert own[0].start < own[1].start < own[2].start
    assert compiles.summary()["misses"] >= 1 and compiles.summary()["hits"] == 0
    assert compiles.totals()[("jit(fresh_a)", "compile", "miss")][0] == 1


def test_the_same_call_after_clear_caches_is_a_hit_with_its_load_time(cache):
    f = _fresh("fresh_b")
    x = jnp.ones(3)
    f(x)
    assert _of("fresh_b")["compile"][0].cache == "miss"
    jax.clear_caches()
    compiles.clear()
    f(x)
    (c,) = _of("fresh_b")["compile"]
    assert c.cache == "hit" and 0 < c.load_s <= c.seconds
    s = compiles.summary()
    assert s["hits"] >= 1 and s["misses"] == 0 and s["load_s"] >= c.load_s
    assert s["programs"] == compiles.requests() == s["hits"]


def test_a_second_call_with_the_same_shapes_calls_no_listener(cache):
    """JAX calls a monitoring listener only when it traces, lowers or
    compiles: counted by four listeners of this test's own, registered
    beside the log's and taken away again."""
    calls = []
    mine = [
        (jax.monitoring.register_event_listener,
         jax.monitoring.unregister_event_listener,
         lambda event, **kw: calls.append(event)),
        (jax.monitoring.register_scalar_listener,
         jax.monitoring.unregister_scalar_listener,
         lambda event, value, **kw: calls.append(event)),
        (jax.monitoring.register_event_duration_secs_listener,
         jax.monitoring.unregister_event_duration_listener,
         lambda event, duration, **kw: calls.append(event)),
        (jax.monitoring.register_event_time_span_listener,
         jax.monitoring.unregister_event_time_span_listener,
         lambda event, start_time, end_time, **kw: calls.append(event)),
    ]
    for register, _, listener in mine:
        register(listener)
    try:
        f = _fresh("fresh_c")
        x = jnp.ones(3)
        f(x)
        assert COMPILE in calls and len(set(calls)) >= 4
        n, kept, asked = len(calls), len(compiles.records()), compiles.requests()
        assert asked > 0
        for _ in range(10):
            jax.block_until_ready(f(x))
        assert (len(calls), len(compiles.records()), compiles.requests()) == (
            n, kept, asked)
    finally:
        for _, unregister, listener in mine:
            unregister(listener)


def test_with_the_cache_off_a_compile_says_so(cache):
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        _fresh("fresh_d")(jnp.ones(3))
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    (c,) = _of("fresh_d")["compile"]
    assert (c.cache, c.load_s) == ("off", None)
    s = compiles.summary()
    assert s["hits"] == s["misses"] == 0 and s["programs"] >= 1


def test_installed_twice_a_compile_is_still_one_record(cache):
    compiles.install()
    compiles.install()
    assert compiles.installed()
    _report("jit(once)", 1)
    assert [r.fun_name for r in compiles.records()] == ["jit(once)"]
    assert compiles.requests() == 1


# ------------------------------------------------- within, and the clock

def test_a_new_shape_inside_a_span_says_which_step_recompiled(cache, tmp_path):
    f = _fresh("fresh_e")
    f(jnp.ones(3))
    obs = _traced_obs()
    compiles.clear()
    with obs.span("zoo.epochs"):
        with obs.span("zoo.dispatch", step=7, epoch=2):
            assert trace_lib.innermost().name == "zoo.dispatch"
            jax.block_until_ready(f(jnp.ones((4, 2))))
        assert trace_lib.innermost().name == "zoo.epochs"
    assert trace_lib.innermost() is None
    got = _of("fresh_e")
    assert sorted(got) == ["compile", "lower", "trace"]
    for r in (r for v in got.values() for r in v):
        assert (r.within, r.ids) == ("zoo.dispatch", {"step": 7, "epoch": 2})
    # on the tracer's clock the records lie inside the span, to within 1 ms
    events = obs.tracer.events()
    (span,) = [e for e in events if e.get("name") == "zoo.dispatch"]
    lo, hi = span["ts"] / 1e6, (span["ts"] + span["dur"]) / 1e6
    for r in (r for v in got.values() for r in v):
        assert lo - 1e-3 <= r.start and r.start + r.span_s <= hi + 1e-3
    # the tracer's events are its own ...
    assert not [e for e in events if e.get("cat") == "compile"]
    # ... and the exported trace shows the records on a lane beside the thread's
    obs.trace_path = str(tmp_path / "t_trace.json")
    with open(obs.finish()["trace"]) as fh:
        written = json.load(fh)["traceEvents"]
    assert written[:len(events)] == events
    lane = [e for e in written if e.get("cat") == "compile"]
    assert lane == [e for e in compiles.trace_events(
        obs.tracer.pid, since=obs.tracer.made) if e["ph"] == "X"]
    assert {e["name"] for e in lane} >= {
        "trace fresh_e", "lower jit(fresh_e)", "compile jit(fresh_e)"}
    assert {e["tid"] for e in lane} == {threading.get_ident() + 1}
    assert {e["pid"] for e in lane} == {obs.tracer.pid}
    assert all(e["args"]["within"] == "zoo.dispatch" and e["args"]["step"] == 7
               for e in lane)
    (c,) = [e for e in lane if e["name"] == "compile jit(fresh_e)"]
    assert c["args"]["cache"] == "miss"
    names = {e["args"]["name"] for e in written
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names == {"MainThread", "MainThread compiles"}
    assert obs_lib.validate_nesting(written) == []


def test_the_offset_between_the_two_clocks_is_taken_to_a_millisecond(cache):
    here = min(abs((time.perf_counter() - time.time()) - compiles._LOG.offset)
               for _ in range(5))
    assert here < 1e-3


def test_an_exported_trace_shows_only_what_was_compiled_since_its_tracer_was_made(
        cache, tmp_path):
    _fresh("fresh_f")(jnp.ones(3))
    obs = _traced_obs()
    obs.trace_path = str(tmp_path / "since_trace.json")
    assert compiles.trace_events(obs.tracer.pid, since=obs.tracer.made) == []
    _fresh("fresh_g")(jnp.ones(3))
    with open(obs.finish()["trace"]) as fh:
        names = {e["name"] for e in json.load(fh)["traceEvents"]
                 if e.get("cat") == "compile"}
    assert "compile jit(fresh_g)" in names and "compile jit(fresh_f)" not in names
    # a tracer alone writes its own events, and whatever it is handed
    path = obs.tracer.export(str(tmp_path / "own.json"))
    with open(path) as fh:
        assert not [e for e in json.load(fh)["traceEvents"]
                    if e.get("cat") == "compile"]
    assert obs_lib.NOOP_TRACER.events() == []
    assert obs_lib.NOOP_TRACER.export(path, extra=[{"ph": "X"}]) is None


def test_the_noop_span_keeps_no_open_span():
    with obs_lib.NOOP.span("zoo.dispatch", step=1):
        assert trace_lib.innermost() is None


# ------------------------------------------------------ bounded, and threads

def _report(name, n, cache=None, inside=None):
    """What JAX reports for `n` compiles of `name`, through JAX's own
    dispatcher: start, (the cache's word,) duration, time span."""
    for i in range(n):
        t = time.time()
        jax.monitoring.record_scalar(COMPILE, t, fun_name=name)
        if cache is not None:
            jax.monitoring.record_event(
                "/jax/compilation_cache/compile_requests_use_cache")
        if cache == "hit":
            jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
            jax.monitoring.record_event_duration_secs(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        if inside is not None:
            inside()
        jax.monitoring.record_event_duration_secs(COMPILE, 1.0, fun_name=name)
        jax.monitoring.record_event_time_span(COMPILE, t, t + 1.0, fun_name=name)


def test_the_log_keeps_the_newest_records_and_every_total(cache):
    n = compiles.KEEP + 500
    _report("jit(flood)", n, cache="hit")
    kept = compiles.records()
    assert len(kept) == compiles.KEEP
    assert all(r.fun_name == "jit(flood)" and r.cache == "hit"
               and r.load_s == 0.25 for r in kept)
    assert compiles.requests() == n
    assert compiles.totals() == {
        ("jit(flood)", "compile", "hit"): (n, pytest.approx(n * 1.0),
                                          pytest.approx(n * 0.25))}
    s = compiles.summary()
    assert (s["programs"], s["hits"], s["misses"]) == (n, n, 0)
    compiles.clear()
    assert compiles.records() == [] and compiles.requests() == 0
    assert compiles.summary()["programs"] == 0 and compiles.totals() == {}


def test_what_ran_inside_a_record_is_taken_off_it(cache):
    """A program compiled while another was being compiled (an eager
    constant inside a traced function is the real case) is kept apart,
    and its whole second comes off the outer record's own time."""
    _report("jit(outer)", 1, cache="miss",
            inside=lambda: _report("jit(inner)", 1))
    inner, outer = compiles.records()
    assert (inner.fun_name, inner.seconds, inner.span_s, inner.cache) == (
        "jit(inner)", 1.0, 1.0, "off")
    assert (outer.fun_name, outer.seconds, outer.span_s, outer.cache) == (
        "jit(outer)", 0.0, 1.0, "miss")


def test_threads_compile_side_by_side_and_no_count_is_lost(cache):
    """More threads than cores, a short switch interval: every thread's
    reports are its own (the cache's word is kept per thread) and no
    increment of a shared total is lost."""
    threads, each = 16, 300
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(
            target=_report, args=(f"jit(w{i})", each, "hit" if i % 2 else "miss"),
            name=f"w{i}") for i in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(switch)
    assert compiles.requests() == threads * each
    totals = compiles.totals()
    assert len(totals) == threads
    for i in range(threads):
        cache_word = "hit" if i % 2 else "miss"
        n, seconds, load_s = totals[(f"jit(w{i})", "compile", cache_word)]
        assert n == each and seconds == pytest.approx(each * 1.0)
        assert load_s == pytest.approx(each * 0.25 if i % 2 else 0.0)
    assert all(r.thread_name == r.fun_name[4:-1] for r in compiles.records())
    s = compiles.summary()
    assert (s["hits"], s["misses"]) == (threads // 2 * each, threads // 2 * each)


# ------------------------------------------------------------- the journal

def test_an_enabled_journal_gets_one_compile_event_a_compile_request(cache, tmp_path):
    bundle = obs_lib.from_config(
        ObsConfig(trace=True, dir=str(tmp_path / "obs"), jax_annotations=False),
        run="j")
    f = _fresh("fresh_h")
    with bundle.span("zoo.dispatch", step=3, epoch=1):
        f(jnp.ones(5))
    asked = compiles.requests()
    assert bundle.journal.counts()["compile"] == asked >= 1
    arts = bundle.finish()
    events = [e for e in obs_lib.read_journal(arts["journal"])
              if e["kind"] == "compile"]
    (mine,) = [e for e in events if e["fun_name"] == "jit(fresh_h)"]
    assert (mine["cache"], mine["within"], mine["step"], mine["epoch"]) == (
        "miss", "zoo.dispatch", 3, 1)
    assert mine["seconds"] > 0 and mine["load_s"] is None
    with open(arts["trace"]) as fh:
        exported = json.load(fh)["traceEvents"]
    assert "compile jit(fresh_h)" in {e.get("name") for e in exported}
    # a finished bundle's journal is closed and detached: nothing raises
    f(jnp.ones(6))
    assert compiles.requests() > asked


# -------------------------------------------- zoo.train's set-up, recorded

def _tiny_model():
    return Sequential([ConvBNAct(8), ConvBNAct(8, relu=False),
                       GlobalAvgPool(), Dense(10)])


class _Epochs:
    def __init__(self):
        self.records = []

    def record(self, **rec):
        self.records.append(rec)


def _train(obs, metrics, **kw):
    x = jax.random.normal(jax.random.key(5), (16, 8, 8, 3))
    y = jnp.arange(16) % 10
    return zoo.train(_tiny_model(), x, y, in_shape=(8, 8, 3), epochs=3,
                     batch_size=8, lr=0.05, seed=1, verbose=False, obs=obs,
                     metrics=metrics, **kw)


def test_zoo_train_with_an_enabled_obs_records_its_set_up(cache, tmp_path):
    bundle = obs_lib.from_config(
        ObsConfig(trace=True, dir=str(tmp_path / "obs"), jax_annotations=False),
        run="zoo")
    epochs = _Epochs()
    _train(bundle, epochs)
    spans = [e for e in bundle.tracer.events() if e.get("cat") == "setup"]
    assert [e["name"] for e in spans] == [
        "zoo.init", "zoo.build_step", "zoo.store", "zoo.catalog"]
    assert all(e["dur"] > 0 and "args" not in e for e in spans)
    # nothing was restored, so no zoo.restore; the loop's spans are as before
    assert obs_lib.validate_nesting(bundle.tracer.events()) == []
    # epoch 1 compiled the step (and set-up's small programs); 2 and 3 nothing
    assert [r["compiles"] for r in epochs.records] == [
        epochs.records[0]["compiles"], 0, 0]
    assert epochs.records[0]["compiles"] > 0
    # the step's own records say where they happened: compiled by the first
    # dispatch; the catalog asks for the same program again (one device: it
    # is still in memory, so tracing is all that is left to record)
    step = _of("step")
    assert [(r.within, r.ids, r.cache) for r in step["compile"]] == [
        ("zoo.dispatch", {"step": 0, "epoch": 1}, "miss")]
    assert [r.within for r in step["trace"]] == ["zoo.dispatch", "zoo.catalog"]
    init = [r for r in compiles.records() if r.within == "zoo.init"]
    assert init and all(r.ids == {} for r in init)
    asked = compiles.requests()
    arts = bundle.finish()
    journal = obs_lib.read_journal(arts["journal"])
    (setup,) = [e for e in journal if e["kind"] == "zoo_setup"]
    assert setup["seq"] < [e for e in journal if e["kind"] == "epoch"][0]["seq"]
    for name in ("init_s", "build_step_s", "store_s", "catalog_s"):
        assert setup[name] > 0, name
    assert "restore_s" not in setup
    # the process's totals when epoch 1 ended: the data's programs too
    assert setup["programs"] == asked >= epochs.records[0]["compiles"]
    assert setup["hits"] + setup["misses"] == setup["programs"]
    for name in ("trace_s", "lower_s", "compile_s"):
        assert setup[name] > 0, name
    assert 0 <= setup["load_s"] < setup["compile_s"]
    assert sum(1 for e in journal if e["kind"] == "compile") == asked


def test_on_a_mesh_the_loop_asks_for_its_second_program_before_the_catalog(cache):
    """Under a mesh the step compiles twice (the state arrives as it was
    made and leaves laid out over the mesh). The loop asks for both
    programs itself, at its first two steps, as an untraced run does; the
    catalog comes when epoch 1's steps are out and is served from jit's
    memory, so a traced run's log holds what an untraced run's holds."""
    from parallel_cnn_tpu.config import MeshConfig
    from parallel_cnn_tpu.parallel import mesh as mesh_lib

    obs = _traced_obs()
    _train(obs, None, mesh=mesh_lib.make_mesh(MeshConfig(data=4)))
    step = _of("step")
    assert [(r.within, r.ids) for r in step["compile"]] == [
        ("zoo.dispatch", {"step": 0, "epoch": 1}),
        ("zoo.dispatch", {"step": 1, "epoch": 1})]
    assert [r.within for r in step["lower"]] == ["zoo.dispatch"] * 2
    assert not [r for r in compiles.records()
                if r.within == "zoo.catalog" and r.kind != "trace"]
    names = [e["name"] for e in obs.tracer.events() if e.get("cat") == "setup"]
    assert names == ["zoo.init", "zoo.build_step", "zoo.store", "zoo.catalog"]
    # the catalog lies after the epoch's last dispatch and before its readback
    by_name = {}
    for e in obs.tracer.events():
        if e.get("ph") == "X" and e.get("args", {}).get("epoch", 1) == 1:
            by_name.setdefault(e["name"], []).append(e)
    (catalog,) = by_name["zoo.catalog"]
    last = max(by_name["zoo.dispatch"], key=lambda e: e["ts"])
    assert last["ts"] + last["dur"] <= catalog["ts"]
    assert catalog["ts"] + catalog["dur"] <= by_name["zoo.readback"][0]["ts"]
    assert obs_lib.programs.lookup("jit_step")  # recorded all the same


def test_zoo_train_without_an_obs_still_fills_the_log_and_opens_no_span(cache):
    epochs = _Epochs()
    _train(None, epochs)
    assert trace_lib.innermost() is None
    assert [r["compiles"] > 0 for r in epochs.records] == [True, False, False]
    assert epochs.records[0]["compiles"] == compiles.requests()
    step = _of("step")
    assert len(step["compile"]) == 1  # no catalog without a tracer
    assert {r.within for r in compiles.records()} == {None}


def test_a_resumed_run_has_a_restore_span(cache, tmp_path):
    ck = str(tmp_path / "ck")
    _train(None, None, checkpoint_dir=ck)
    obs = _traced_obs()
    x = jax.random.normal(jax.random.key(5), (16, 8, 8, 3))
    zoo.train(_tiny_model(), x, jnp.arange(16) % 10, in_shape=(8, 8, 3),
              epochs=4, batch_size=8, lr=0.05, seed=1, verbose=False, obs=obs,
              checkpoint_dir=ck, resume=True)
    names = [e["name"] for e in obs.tracer.events() if e.get("cat") == "setup"]
    assert names == ["zoo.init", "zoo.build_step", "zoo.restore", "zoo.store",
                     "zoo.catalog"]
