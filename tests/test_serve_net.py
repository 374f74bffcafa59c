"""Network front-door tests: serve/net.py, serve/supervisor.py, and the
persistent AOT-executable cache in serve/engine.py.

Everything runs over real loopback sockets against the tiny Dense
handle (ms-fast AOT compiles). The recurring judgment is the wire-tier
conservation law — ``submitted == completed + shed + expired + failed``
on the WireStats shared across endpoint incarnations — plus the three
robustness contracts of the PR: a slow-loris connection is reaped as
*expired* (never a hung handler), a killed endpoint journals its
in-flight requests as ``net_failed`` and the supervisor's respawn keeps
the same port, and a weight hot-swap under live traffic finishes with
zero failed requests.
"""

import json
import os
import socket
import time
import warnings

import numpy as np
import pytest

from parallel_cnn_tpu.config import NetConfig, ServeConfig
from parallel_cnn_tpu.nn.core import Sequential
from parallel_cnn_tpu.nn.layers import Dense, Flatten
from parallel_cnn_tpu.resilience.chaos import ChaosMonkey
from parallel_cnn_tpu.resilience.retry import RetryPolicy
from parallel_cnn_tpu.serve import scenarios, serve_stack
from parallel_cnn_tpu.serve.engine import (
    AotCacheWarning,
    Engine,
    ReplicaPool,
    load_or_init,
)
from parallel_cnn_tpu.serve.loadgen import (
    NetClient,
    NetTransportError,
    run_closed_loop_net,
)
from parallel_cnn_tpu.serve.net import NetServer, encode_request
from parallel_cnn_tpu.serve.registry import ModelHandle
from parallel_cnn_tpu.serve.supervisor import Supervisor, hot_swap
from parallel_cnn_tpu.serve.telemetry import ServeStats, WireStats

pytestmark = pytest.mark.serve_net

IN_SHAPE = (4, 3)


def tiny_handle() -> ModelHandle:
    model = Sequential([Flatten(), Dense(8)])

    def init(key):
        params, state, _ = model.init(key, IN_SHAPE)
        return params, state

    def forward(params, state, x):
        return model.apply(params, state, x, train=False)[0]

    return ModelHandle("tiny", IN_SHAPE, 8, init, forward)


@pytest.fixture
def stack():
    """A started (pool, batcher) on one device, closed at teardown."""
    import jax

    cfg = ServeConfig(max_batch=8, queue_depth=64, max_wait_ms=2.0)
    pool, batcher = serve_stack(
        tiny_handle(), cfg, devices=jax.devices()[:1], stats=ServeStats(),
        start=True,
    )
    yield pool, batcher
    batcher.close()


def _server(batcher, **kw):
    kw.setdefault("conn_deadline_ms", 1000.0)
    return NetServer(batcher, **kw).start()


# -- NetConfig (config.py satellite) ------------------------------------


def test_net_config_env_layering(monkeypatch):
    monkeypatch.setenv("PCNN_SERVE_LISTEN", "1")
    monkeypatch.setenv("PCNN_SERVE_PORT", "8123")
    monkeypatch.setenv("PCNN_SERVE_CONN_DEADLINE_MS", "750")
    monkeypatch.setenv("PCNN_SERVE_AOT_CACHE_DIR", "/tmp/x")
    monkeypatch.setenv("PCNN_SERVE_SUPERVISE", "true")
    monkeypatch.setenv("PCNN_SERVE_RESPAWN_ATTEMPTS", "7")
    nc = NetConfig.from_env()
    assert nc.listen and nc.supervise
    assert nc.port == 8123
    assert nc.conn_deadline_ms == 750.0
    assert nc.aot_cache_dir == "/tmp/x"
    assert nc.respawn_attempts == 7
    # Unset fields keep dataclass defaults (no-sentinel idiom).
    assert nc.host == "127.0.0.1"


def test_net_config_validation():
    with pytest.raises(ValueError):
        NetConfig(port=70000)
    with pytest.raises(ValueError):
        NetConfig(conn_deadline_ms=0.0)
    with pytest.raises(ValueError):
        NetConfig(respawn_attempts=0)


# -- protocol round trip + wire conservation ----------------------------


def test_round_trip_and_wire_conservation(stack):
    _, batcher = stack
    wire = WireStats()
    with _server(batcher, wire=wire) as srv:
        with NetClient(srv.address, timeout_s=10.0) as nc:
            y = nc.request(np.zeros(IN_SHAPE, np.float32))
            assert y.shape == (8,)
            # Explicit deadline rides the guaranteed class; absent one
            # rides best-effort — both resolve as completed.
            nc.request(np.ones(IN_SHAPE, np.float32), deadline_ms=2000.0)
        # the endpoint counts an outcome after it has written the reply
        deadline = time.monotonic() + 2.0
        while wire.snapshot()["completed"] < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        snap = wire.snapshot()
        assert snap["submitted"] == 2 == snap["completed"]
        assert wire.balanced()
        assert snap["conn_opened"] == 1


def test_bad_request_is_failed_not_crash(stack):
    _, batcher = stack
    wire = WireStats()
    with _server(batcher, wire=wire) as srv:
        s = socket.create_connection(srv.address, timeout=5.0)
        try:
            s.sendall(b'{"id": 1, "nope": true}\n')
            reply = json.loads(s.makefile().readline())
            assert reply["ok"] is False and reply["error"] == "BadRequest"
            # The connection survives a bad request; a good one follows.
            s.sendall(encode_request(2, np.zeros(IN_SHAPE, np.float32)))
            reply = json.loads(s.makefile().readline())
            assert reply["ok"] is True
        finally:
            s.close()
        # the endpoint counts an outcome after it has written the reply
        deadline = time.monotonic() + 2.0
        while wire.snapshot()["completed"] < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        snap = wire.snapshot()
        assert snap["failed"] == 1 and snap["completed"] == 1
        assert wire.balanced()


def test_closed_loop_net_conservation(stack):
    _, batcher = stack
    wire = WireStats()
    with _server(batcher, wire=wire) as srv:
        rep = run_closed_loop_net(
            srv.address,
            np.zeros((4, *IN_SHAPE), np.float32),
            n_requests=32, concurrency=4, seed=0,
        )
    assert rep.completed == 32 and rep.errors == 0
    assert wire.balanced()
    assert wire.snapshot()["submitted"] == 32


# -- slow-loris: reaped as expired, never hung --------------------------


def test_slow_loris_reaped_as_expired(stack):
    _, batcher = stack
    wire = WireStats()
    with _server(batcher, wire=wire, conn_deadline_ms=150.0) as srv:
        chaos = ChaosMonkey.from_spec("slow-loris@3:400")
        rep = run_closed_loop_net(
            srv.address, np.zeros((2, *IN_SHAPE), np.float32),
            n_requests=16, concurrency=2, seed=0, chaos=chaos,
        )
        assert chaos.slow_loris_fired
        assert rep.expired == 1          # the loris victim, client view
        assert rep.completed == 15
        snap = wire.snapshot()
        assert snap["reaped"] == 1       # server reaped the partial
        assert snap["expired"] == 1
        assert wire.balanced()
        # Not hung: the endpoint still answers promptly after the reap.
        with NetClient(srv.address, timeout_s=5.0) as nc:
            t0 = time.monotonic()
            nc.request(np.zeros(IN_SHAPE, np.float32))
            assert time.monotonic() - t0 < 5.0


def test_idle_connection_closes_quietly(stack):
    """An idle keep-alive gap is not an attack: timeout with an empty
    buffer closes the conn without touching the conservation sum."""
    _, batcher = stack
    wire = WireStats()
    with _server(batcher, wire=wire, conn_deadline_ms=100.0) as srv:
        s = socket.create_connection(srv.address, timeout=5.0)
        try:
            assert s.recv(1) == b""      # server closed on idle timeout
        finally:
            s.close()
        deadline = time.monotonic() + 2.0
        while wire.snapshot()["conn_closed"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        snap = wire.snapshot()
        assert snap["submitted"] == 0 and snap["reaped"] == 0


# -- kill-endpoint + supervisor -----------------------------------------


def _supervised(batcher, wire, spec, attempts=6):
    """A supervisor whose FIRST incarnation is chaos-armed; respawns
    come up clean (one-shot chaos must not replay across restarts)."""
    armed = [ChaosMonkey.from_spec(spec)]

    def factory(port, seq_start):
        m = armed.pop(0) if armed else None
        return NetServer(batcher, port=port, conn_deadline_ms=1000.0,
                         wire=wire, chaos=m, seq_start=seq_start).start()

    return Supervisor(
        factory,
        policy=RetryPolicy(attempts=attempts, base_delay=0.02,
                           max_delay=0.2, seed=0),
    ).start()


def test_kill_endpoint_conservation_across_respawn(stack):
    _, batcher = stack
    wire = WireStats()
    sup = _supervised(batcher, wire, "kill-endpoint@12")
    try:
        rep = scenarios.run_net(
            "net-kill-endpoint", batcher, wire=wire, supervisor=sup,
            retry=RetryPolicy(attempts=8, base_delay=0.05, max_delay=0.5,
                              seed=1),
        )
        assert rep.passed, rep.to_dict()
        assert rep.errors == 0           # retries rode through the respawn
        assert sup.respawns >= 1
        assert rep.wire["endpoint_deaths"] == 1
        # In-flight wire requests at death were journaled failed — and
        # the law still balances including them.
        assert rep.wire["failed"] >= 0
        assert rep.wire["submitted"] == (
            rep.wire["completed"] + rep.wire["shed"]
            + rep.wire["expired"] + rep.wire["failed"]
        )
        # Same port across incarnations (the supervisor contract).
        assert not sup.gave_up
    finally:
        sup.close()


def test_unsupervised_kill_trips_the_gate(stack):
    """The anti-vacuity control arm: same fault, supervision disabled —
    clients exhaust retries and the scenario must FAIL."""
    _, batcher = stack
    wire = WireStats()
    armed = [ChaosMonkey.from_spec("kill-endpoint@12")]

    def factory(port, seq_start):
        m = armed.pop(0) if armed else None
        return NetServer(batcher, port=port, conn_deadline_ms=1000.0,
                         wire=wire, chaos=m, seq_start=seq_start).start()

    sup = Supervisor(factory, enabled=False).start()
    try:
        rep = scenarios.run_net(
            "net-kill-endpoint", batcher, wire=wire, supervisor=sup,
            retry=RetryPolicy(attempts=3, base_delay=0.01, max_delay=0.05,
                              seed=1),
        )
        assert not rep.passed
        assert rep.errors > 0
        assert wire.balanced()           # even the failure is accounted
    finally:
        sup.close()


def test_killed_endpoint_fails_inflight_and_drops_clients(stack):
    _, batcher = stack
    wire = WireStats()
    with _server(batcher, wire=wire) as srv:
        with NetClient(srv.address, timeout_s=5.0) as nc:
            nc.request(np.zeros(IN_SHAPE, np.float32))
            srv.kill(reason="test")
            with pytest.raises(NetTransportError):
                nc.request(np.zeros(IN_SHAPE, np.float32))
        assert not srv.alive
        snap = wire.snapshot()
        assert snap["endpoint_deaths"] == 1
        assert wire.balanced()


# -- persistent AOT-executable cache ------------------------------------


def _engine(tmp_path, seed=0, **kw):
    return Engine(tiny_handle(), max_batch=4, seed=seed,
                  cache_dir=str(tmp_path), **kw)


def test_aot_cache_warm_start_zero_compiles(tmp_path):
    cold = _engine(tmp_path)
    cold.precompile()
    assert cold.stats.aot_cache_misses > 0
    assert cold.stats.aot_cache_hits == 0
    n_entries = len(list(tmp_path.glob("*.aotx")))
    assert n_entries == cold.stats.aot_cache_misses

    warm = _engine(tmp_path)
    warm.precompile()
    # The tentpole assertion: a warm cold-start issues ZERO compiles.
    assert warm.stats.aot_compiles == 0
    assert warm.stats.aot_cache_hits == n_entries
    assert warm.stats.aot_cache_misses == 0
    # And the restored executables actually serve.
    x = np.zeros((2, *IN_SHAPE), np.float32)
    np.testing.assert_allclose(warm.predict(x), cold.predict(x),
                               rtol=0, atol=0)


@pytest.mark.parametrize("damage", ["truncate", "corrupt_payload",
                                    "bad_magic"])
def test_aot_cache_corruption_degrades_to_recompile(tmp_path, damage):
    cold = _engine(tmp_path)
    cold.precompile()
    victim = sorted(tmp_path.glob("*.aotx"))[0]
    raw = victim.read_bytes()
    if damage == "truncate":
        victim.write_bytes(raw[: len(raw) // 2])
    elif damage == "corrupt_payload":
        flipped = bytearray(raw)
        flipped[-20] ^= 0xFF
        victim.write_bytes(bytes(flipped))
    else:
        victim.write_bytes(b"JUNK" + raw[4:])
    with pytest.warns(AotCacheWarning):
        eng = _engine(tmp_path)
        eng.precompile()
    # Typed degrade, never a crash: the damaged bucket recompiled, the
    # intact ones still hit.
    assert eng.stats.aot_cache_corrupt == 1
    assert eng.stats.aot_compiles == 1
    assert eng.stats.aot_cache_hits == cold.stats.aot_cache_misses - 1
    # The corrupt entry was atomically rewritten: a third start is clean.
    clean = _engine(tmp_path)
    clean.precompile()
    assert clean.stats.aot_compiles == 0
    assert clean.stats.aot_cache_corrupt == 0


def test_aot_cache_fingerprint_mismatch_on_new_weights(tmp_path):
    _engine(tmp_path, seed=0).precompile()
    # Different weights → params digest differs → every entry is a typed
    # mismatch (stale executables bake in the old weights; silently
    # serving them would be a wrong-answer bug, not a perf bug).
    with pytest.warns(AotCacheWarning, match="fingerprint"):
        eng = _engine(tmp_path, seed=7)
        eng.precompile()
    assert eng.stats.aot_cache_corrupt > 0
    assert eng.stats.aot_compiles > 0


def test_aot_cache_events_journaled(tmp_path):
    from parallel_cnn_tpu import obs as obs_lib
    from parallel_cnn_tpu.config import ObsConfig

    out = tmp_path / "obs"
    bundle = obs_lib.from_config(
        ObsConfig(trace=True, dir=str(out)), run="aot-cache-test",
    )
    cache = tmp_path / "cache"
    Engine(tiny_handle(), max_batch=4, cache_dir=str(cache),
           obs=bundle).precompile()
    Engine(tiny_handle(), max_batch=4, cache_dir=str(cache),
           obs=bundle).precompile()
    counts = bundle.journal.counts()
    bundle.finish()
    assert counts.get("aot_cache_miss", 0) > 0
    assert counts.get("aot_cache_hit", 0) > 0


# -- hot swap -----------------------------------------------------------


def test_hot_swap_zero_failed_under_live_traffic(stack):
    pool, batcher = stack
    wire = WireStats()
    with _server(batcher, wire=wire, conn_deadline_ms=3000.0) as srv:
        new_params, new_state = load_or_init(pool.handle, seed=7)
        rep = scenarios.run_net(
            "net-hot-swap-diurnal", batcher, wire=wire, server=srv,
            swap_params=new_params, swap_state=new_state,
        )
        assert rep.passed, rep.to_dict()
        assert rep.swap["failed_delta"] == 0
        assert rep.swap["stuck"] == []
        assert len(rep.swap["swapped"]) >= 1
        assert wire.balanced()


def test_hot_swap_replicas_serve_new_weights():
    """After the roll, predictions come from the NEW weights (the swap
    is real, not just a pool shuffle)."""
    import jax

    cfg = ServeConfig(max_batch=8, queue_depth=64, max_wait_ms=2.0)
    pool, batcher = serve_stack(
        tiny_handle(), cfg, devices=jax.devices()[:1], start=True,
    )
    try:
        x = np.ones((1, *IN_SHAPE), np.float32)
        y_old = np.array(pool.engines[pool.next_replica()].predict(x))
        new_params, new_state = load_or_init(pool.handle, seed=7)
        report = hot_swap(pool, batcher, new_params, new_state)
        assert report["failed_delta"] == 0 and not report["stuck"]
        fresh = ReplicaPool(tiny_handle(), max_batch=8, seed=7)
        y_ref = np.array(fresh.engines[0].predict(x))
        y_new = np.array(pool.engines[pool.next_replica()].predict(x))
        np.testing.assert_allclose(y_new, y_ref, rtol=0, atol=1e-6)
        assert not np.allclose(y_new, y_old)
    finally:
        batcher.close()


def test_hot_swap_invalidates_aot_cache_entries(tmp_path):
    """The cache key includes the params digest: weights swapped on the
    pool make the old disk entries typed mismatches for replicas built
    after the swap — never silently-stale executables."""
    import jax

    # One device on purpose: the grown replica must land on the SAME
    # device so it reads the seed-0 entries (filenames are per-device).
    pool = ReplicaPool(tiny_handle(), max_batch=4, seed=0,
                       cache_dir=str(tmp_path), precompile=True,
                       devices=jax.devices()[:1])
    new_params, new_state = load_or_init(pool.handle, seed=7)
    pool.set_weights(new_params, new_state)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any stray warning fails loudly
        with pytest.warns(AotCacheWarning, match="fingerprint"):
            i = pool.grow()
            pool.engines[i].precompile()


# -- chaos grammar (resilience/chaos.py satellite) ----------------------


def test_chaos_spec_grammar_net_kinds():
    m = ChaosMonkey.from_spec("kill-endpoint@5")
    assert m.kill_endpoint_seq == 5
    assert not m.kill_endpoint_at(4)
    assert m.kill_endpoint_at(5)
    assert not m.kill_endpoint_at(6)     # one-shot
    m = ChaosMonkey.from_spec("slow-loris@3:250")
    assert m.slow_loris == (3, 250.0)
    assert m.slow_loris_at(2) is None
    assert m.slow_loris_at(3) == 250.0
    assert m.slow_loris_at(4) is None    # one-shot
    with pytest.raises(ValueError):
        ChaosMonkey.from_spec("kill-endpoint@")
    with pytest.raises(ValueError):
        ChaosMonkey.from_spec("slow-loris@3")
