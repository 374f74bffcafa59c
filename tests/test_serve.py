"""Serving subsystem tests (serve/): bucket padding round-trip against a
jit-forward oracle, AOT compile-cache accounting, dynamic-batcher
coalescing / deadline expiry / shed-under-overload, deterministic replica
round-robin, inference-only checkpoint restore, the streaming latency
histogram, and the loadgen patterns. Everything runs on a tiny Dense
model so the whole module stays tier-1 fast on CPU.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_cnn_tpu.config import ServeConfig
from parallel_cnn_tpu.nn.core import Sequential
from parallel_cnn_tpu.nn.layers import Dense, Flatten
from parallel_cnn_tpu.serve import (
    DeadlineExceeded,
    DynamicBatcher,
    Engine,
    Overloaded,
    ReplicaPool,
    available,
    bucket_for,
    get,
    loadgen,
    serve_stack,
)
from parallel_cnn_tpu.serve.registry import ModelHandle
from parallel_cnn_tpu.train import checkpoint
from parallel_cnn_tpu.train.zoo import ZooState
from parallel_cnn_tpu.utils.metrics import Histogram

pytestmark = pytest.mark.serve

IN_SHAPE = (4, 3)


def tiny_handle() -> ModelHandle:
    """Smallest real Module pipeline: flatten → dense(8). Fast enough
    that every AOT bucket compiles in milliseconds."""
    model = Sequential([Flatten(), Dense(8)])

    def init(key):
        params, state, _ = model.init(key, IN_SHAPE)
        return params, state

    def forward(params, state, x):
        return model.apply(params, state, x, train=False)[0]

    return ModelHandle("tiny", IN_SHAPE, 8, init, forward)


def tiny_cfg(**kw) -> ServeConfig:
    base = dict(model="cifar_cnn", max_batch=4, max_wait_ms=5.0,
                queue_depth=64)
    base.update(kw)
    return ServeConfig(**base)


# -- histogram (utils/metrics.py satellite) -----------------------------


def test_histogram_percentiles_within_bin_error():
    h = Histogram(lo=1e-4, hi=10.0, bins=128)
    rng = np.random.default_rng(0)
    xs = rng.uniform(0.001, 1.0, 5000)
    for x in xs:
        h.record(x)
    ratio = (10.0 / 1e-4) ** (1.0 / 128)  # max relative bin error
    for p in (50, 90, 99):
        exact = float(np.percentile(xs, p))
        got = h.percentile(p)
        assert exact / ratio <= got <= exact * ratio, (p, got, exact)
    assert h.count == 5000
    assert abs(h.mean - xs.mean()) < 1e-9  # sum is exact, not binned


def test_histogram_single_sample_clamps_to_observed():
    h = Histogram()
    h.record(0.0123)
    # A lone sample must come back exactly (clamped into [min, max]),
    # not as the geometric midpoint of whatever bin it landed in.
    assert h.percentile(50) == pytest.approx(0.0123)
    assert h.summary(scale=1e3)["p99"] == pytest.approx(12.3)


def test_histogram_out_of_range_and_empty():
    h = Histogram(lo=1e-3, hi=1.0, bins=8)
    assert h.percentile(50) is None
    assert h.summary() == {"count": 0}
    h.record(1e-9)   # below lo: first bin, still counted
    h.record(1e9)    # above hi: last bin, still counted
    assert h.count == 2
    assert h.min == 1e-9 and h.max == 1e9


def test_histogram_merge_and_validation():
    a, b = Histogram(bins=32), Histogram(bins=32)
    for v in (0.01, 0.02):
        a.record(v)
    for v in (0.04, 0.08):
        b.record(v)
    a.merge(b)
    assert a.count == 4 and a.min == 0.01 and a.max == 0.08
    with pytest.raises(ValueError):
        a.merge(Histogram(bins=16))
    with pytest.raises(ValueError):
        Histogram(lo=1.0, hi=0.5)
    with pytest.raises(ValueError):
        a.percentile(101)


# -- inference-only restore (train/checkpoint.py satellite) -------------


def test_load_params_ignores_optimizer_state(tmp_path):
    full = ZooState(
        params={"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
        model_state={"bn_mean": np.ones(3, np.float32)},
        opt_state={"momentum": np.full((2, 3), 7.0, np.float32)},
    )
    path = str(tmp_path / "ckpt_1.npz")
    checkpoint.save(path, full)
    like = ZooState(
        params={"w": np.zeros((2, 3), np.float32)},
        model_state={"bn_mean": np.zeros(3, np.float32)},
        opt_state={},  # empty → no leaves → stored momentum is surplus
    )
    got = checkpoint.load_params(path, like)
    np.testing.assert_array_equal(np.asarray(got.params["w"]),
                                  full.params["w"])
    np.testing.assert_array_equal(np.asarray(got.model_state["bn_mean"]),
                                  full.model_state["bn_mean"])
    assert got.opt_state == {}
    # restore() keeps its exact-match contract: the surplus opt_state
    # leaves make the same template a structure mismatch there.
    with pytest.raises(ValueError, match="surplus"):
        checkpoint.restore(path, like)


def test_load_params_typed_errors(tmp_path):
    params = {"w": np.ones((2, 2), np.float32)}
    path = str(tmp_path / "ckpt_1.npz")
    checkpoint.save(path, params)

    # missing wanted leaf
    with pytest.raises(ValueError, match="lacks required leaves"):
        checkpoint.load_params(path, {"w": params["w"], "extra": params["w"]})
    # shape mismatch on a wanted leaf
    with pytest.raises(ValueError, match="expected"):
        checkpoint.load_params(path, {"w": np.ones((3, 3), np.float32)})
    # torn/corrupt file → the shared typed error
    torn = str(tmp_path / "torn.npz")
    with open(path, "rb") as f:
        blob = f.read()
    with open(torn, "wb") as f:
        f.write(blob[: len(blob) // 2])
    with pytest.raises(ValueError, match="corrupted or unreadable"):
        checkpoint.load_params(torn, params)
    # version mismatch → same typed error family
    import json as json_mod

    stored = dict(np.load(path))
    stored["__meta__"] = np.frombuffer(
        json_mod.dumps({"version": 999, "epoch": 0, "epoch_errors": [],
                        "extra": {}}).encode(), dtype=np.uint8)
    skewed = str(tmp_path / "skewed.npz")
    np.savez(skewed, **stored)
    with pytest.raises(ValueError, match="version"):
        checkpoint.load_params(skewed, params)


def test_engine_restores_zoo_checkpoint(tmp_path):
    handle = tiny_handle()
    params, state = handle.init(jax.random.key(3))
    # Fake a full training checkpoint: real params/state + an optimizer
    # blob the engine must be able to ignore.
    full = ZooState(params, state,
                    {"mom": jax.tree_util.tree_map(np.asarray, params)})
    path = str(tmp_path / "ckpt_9.npz")
    checkpoint.save(path, full)
    eng = Engine(handle, checkpoint=path, max_batch=2, seed=99)
    x = np.ones((2, *IN_SHAPE), np.float32)
    want = np.asarray(jax.jit(
        lambda v: handle.forward(params, state, v))(jnp.asarray(x)))
    np.testing.assert_array_equal(eng.predict(x), want)


# -- buckets + engine ---------------------------------------------------


def test_bucket_for_mapping():
    assert [bucket_for(n, 8) for n in (1, 2, 3, 4, 5, 7, 8)] == \
        [1, 2, 4, 4, 8, 8, 8]
    with pytest.raises(ValueError, match="exceeds max_batch"):
        bucket_for(9, 8)
    with pytest.raises(ValueError, match="at least one"):
        bucket_for(0, 8)
    with pytest.raises(ValueError, match="power of two"):
        Engine(tiny_handle(), max_batch=6)


def test_engine_padding_roundtrip_bitwise():
    """The padding contract: engine output at every n ≤ max_batch equals
    (bit-for-bit) a jit forward of the same weights at the padded bucket
    shape, sliced back to n."""
    handle = tiny_handle()
    eng = Engine(handle, max_batch=4, seed=0)
    ref = jax.jit(lambda v: handle.forward(eng._params, eng._state, v))
    rng = np.random.default_rng(1)
    for n in (1, 2, 3, 4):
        x = rng.uniform(-1, 1, (n, *IN_SHAPE)).astype(np.float32)
        got = eng.predict(x)
        assert got.shape == (n, 8)
        b = eng.bucket_for(n)
        padded = np.concatenate(
            [x, np.zeros((b - n, *IN_SHAPE), np.float32)])
        want = np.asarray(ref(jnp.asarray(padded)))[:n]
        assert np.array_equal(got, want), f"n={n} bucket={b}"


def test_engine_aot_cache_accounting():
    eng = Engine(tiny_handle(), max_batch=4)
    assert eng.buckets == [1, 2, 4]
    x = np.zeros((3, *IN_SHAPE), np.float32)
    eng.predict(x)                       # compiles bucket 4
    assert (eng.stats.aot_compiles, eng.stats.aot_hits) == (1, 0)
    eng.predict(x)                       # cache hit
    eng.predict(x[:1])                   # compiles bucket 1
    assert (eng.stats.aot_compiles, eng.stats.aot_hits) == (2, 1)
    timings = eng.precompile()           # fills bucket 2 only
    assert (eng.stats.aot_compiles, eng.stats.aot_hits) == (3, 1)
    assert set(timings) == {1, 2, 4}
    eng.precompile()                     # idempotent, no hit inflation
    assert (eng.stats.aot_compiles, eng.stats.aot_hits) == (3, 1)
    assert eng.stats.predicts == 3


def test_engine_rejects_wrong_shape():
    eng = Engine(tiny_handle(), max_batch=2)
    with pytest.raises(ValueError, match="expected"):
        eng.predict(np.zeros((1, 5, 3), np.float32))
    with pytest.raises(ValueError, match="exceeds max_batch"):
        eng.predict(np.zeros((3, *IN_SHAPE), np.float32))


# -- dynamic batcher ----------------------------------------------------


def test_batcher_coalesces_and_splits():
    handle = tiny_handle()
    pool = ReplicaPool(handle, max_batch=4)
    batcher = DynamicBatcher(pool, max_wait_ms=20.0, queue_depth=64,
                             start=False)
    rng = np.random.default_rng(2)
    xs = rng.uniform(0, 1, (4, *IN_SHAPE)).astype(np.float32)
    futs = [batcher.submit(x) for x in xs]
    batcher.start()
    try:
        got = np.stack([f.result(timeout=30.0) for f in futs])
        want = pool.engines[0].predict(xs)
        np.testing.assert_array_equal(got, want)
        # All 4 were queued before the worker started → one full batch.
        assert batcher.stats.batches == 1
        assert batcher.stats.mean_occupancy() == 1.0
        assert all(f.batch_seq == 0 for f in futs)
    finally:
        batcher.close()


def test_batcher_deadline_expiry():
    pool = ReplicaPool(tiny_handle(), max_batch=4)
    batcher = DynamicBatcher(pool, max_wait_ms=1.0, queue_depth=8,
                             start=False)
    x = np.zeros(IN_SHAPE, np.float32)
    doomed = batcher.submit(x, deadline_ms=1.0)
    alive = batcher.submit(x)  # no deadline
    time.sleep(0.05)           # let the 1 ms budget lapse pre-dispatch
    batcher.start()
    try:
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=30.0)
        assert alive.result(timeout=30.0).shape == (8,)
        assert batcher.stats.expired == 1
        assert batcher.stats.completed == 1
    finally:
        batcher.close()


def test_batcher_sheds_when_queue_full():
    pool = ReplicaPool(tiny_handle(), max_batch=4)
    batcher = DynamicBatcher(pool, queue_depth=2, start=False)
    x = np.zeros(IN_SHAPE, np.float32)
    batcher.submit(x)
    batcher.submit(x)
    with pytest.raises(Overloaded, match="back off and retry"):
        batcher.submit(x)
    assert batcher.stats.shed == 1
    assert batcher.stats.submitted == 3
    assert batcher.stats.shed_rate() == pytest.approx(1 / 3)
    batcher.close()


def test_batcher_close_fails_pending_futures():
    pool = ReplicaPool(tiny_handle(), max_batch=2)
    batcher = DynamicBatcher(pool, queue_depth=8, start=False)
    fut = batcher.submit(np.zeros(IN_SHAPE, np.float32))
    batcher.close()
    with pytest.raises(RuntimeError, match="batcher closed"):
        fut.result(timeout=5.0)


def test_replica_round_robin_deterministic():
    """Batches formed in a known order land on replicas 0,1,0,1 — the
    assignment happens in the single worker thread at batch-formation
    time, so it replays exactly regardless of runner scheduling."""
    pool = ReplicaPool(tiny_handle(), n_replicas=2, max_batch=1)
    batcher = DynamicBatcher(pool, max_wait_ms=0.0, queue_depth=16,
                             start=False)
    x = np.zeros(IN_SHAPE, np.float32)
    futs = [batcher.submit(x) for _ in range(4)]
    batcher.start()
    try:
        for f in futs:
            f.result(timeout=30.0)
        assert [f.replica for f in futs] == [0, 1, 0, 1]
        assert [f.batch_seq for f in futs] == [0, 1, 2, 3]
        assert batcher.stats.replica_batches == {0: 2, 1: 2}
    finally:
        batcher.close()


def test_pool_pins_engines_across_devices():
    devices = jax.devices()
    pool = ReplicaPool(tiny_handle(), n_replicas=3, max_batch=2,
                       devices=devices)
    want = [devices[i % len(devices)] for i in range(3)]
    assert [e.device for e in pool.engines] == want
    assert [pool.next_replica() for _ in range(4)] == [0, 1, 2, 0]


# -- loadgen ------------------------------------------------------------


def test_loadgen_closed_loop_completes_without_shedding():
    handle = tiny_handle()
    _, batcher = serve_stack(handle, tiny_cfg(max_batch=4, queue_depth=64))
    with batcher:
        report = loadgen.run(batcher, pattern="closed", n_requests=24,
                             concurrency=4, seed=0)
    assert report.completed == 24
    assert report.shed_rate == 0.0
    assert report.latency.count == 24
    assert report.to_dict()["latency_ms"]["p99"] > 0


def test_loadgen_open_loop_poisson():
    handle = tiny_handle()
    _, batcher = serve_stack(handle, tiny_cfg(max_batch=4, queue_depth=64))
    with batcher:
        report = loadgen.run(batcher, pattern="open", n_requests=16,
                             rate=2000.0, seed=3)
    assert report.pattern == "open"
    assert report.offered_rate == 2000.0
    assert report.completed + report.shed + report.expired == 16
    assert report.shed == 0  # queue_depth 64 >> 16 in-flight
    with pytest.raises(ValueError, match="rate"):
        loadgen.run(batcher, pattern="open", n_requests=1, rate=0.0)
    with pytest.raises(ValueError, match="unknown pattern"):
        loadgen.run(batcher, pattern="bursty", n_requests=1)


def test_loadgen_retries_resubmit_sheds():
    """Closed-loop clients retry Overloaded submits with backoff; with a
    tiny queue but a live worker, every request eventually lands."""
    handle = tiny_handle()
    _, batcher = serve_stack(
        handle, tiny_cfg(max_batch=2, queue_depth=2, max_wait_ms=0.5))
    with batcher:
        report = loadgen.run(batcher, pattern="closed", n_requests=32,
                             concurrency=8, seed=1)
    assert report.completed + report.shed == 32
    assert report.completed >= 24  # retries recover most contention


# -- config + registry --------------------------------------------------


def test_serve_config_validation_and_env(monkeypatch):
    with pytest.raises(ValueError, match="power of two"):
        ServeConfig(max_batch=12)
    with pytest.raises(ValueError, match="n_replicas"):
        ServeConfig(n_replicas=0)
    with pytest.raises(ValueError, match="queue_depth"):
        ServeConfig(queue_depth=0)
    monkeypatch.setenv("PCNN_SERVE_MODEL", "resnet18")
    monkeypatch.setenv("PCNN_SERVE_MAX_BATCH", "32")
    monkeypatch.setenv("PCNN_SERVE_MAX_WAIT_MS", "7.5")
    monkeypatch.setenv("PCNN_SERVE_REPLICAS", "2")
    monkeypatch.setenv("PCNN_SERVE_DEADLINE_MS", "50")
    monkeypatch.setenv("PCNN_SERVE_PRECOMPILE", "0")
    sc = ServeConfig.from_env()
    assert (sc.model, sc.max_batch, sc.max_wait_ms) == ("resnet18", 32, 7.5)
    assert (sc.n_replicas, sc.deadline_ms, sc.precompile) == (2, 50.0, False)


def test_registry_names_and_errors():
    assert set(available()) >= {"lenet_ref", "cifar_cnn", "resnet18",
                                "vgg16"}
    h = get("lenet_ref")
    assert h.in_shape == (28, 28) and h.n_outputs == 10
    with pytest.raises(KeyError, match="unknown model"):
        get("alexnet")
    with pytest.raises(ValueError, match="resnet/vgg"):
        get("cifar_cnn", conv_backend="pallas")


def test_lenet_handle_serves_end_to_end():
    """One non-tiny model through the whole stack: registry → engine →
    batcher → result, proving the lenet dialect (bare params, vmapped
    functional forward) serves like the zoo dialect."""
    handle = get("lenet_ref")
    _, batcher = serve_stack(
        handle,
        ServeConfig(model="lenet_ref", max_batch=2, max_wait_ms=2.0,
                    queue_depth=8),
    )
    with batcher:
        x = np.zeros((28, 28), np.float32)
        y = batcher.submit(x).result(timeout=60.0)
    assert y.shape == (10,)
    assert np.all(np.isfinite(y))


# ------------------------------------------------ serve CLI exit codes


def _serve_cli(monkeypatch, *extra):
    from parallel_cnn_tpu import cli
    from parallel_cnn_tpu.utils import backend

    # In-process CLI under pytest: leave the session's compile cache alone.
    monkeypatch.setattr(backend, "enable_compile_cache", lambda: "")
    return cli.main(["serve", "--model", "lenet_ref", "--requests", "8",
                     "--max-batch", "4", *extra])


def test_serve_cli_clean_run_returns_zero(monkeypatch, tmp_path):
    out = tmp_path / "serve.json"
    assert _serve_cli(monkeypatch, "--json", str(out)) == 0
    rep = json.loads(out.read_text())
    assert rep["parity"] == {"n": 3, "bucket": 4, "max_abs_diff": 0.0}
    assert rep["report"]["completed"] == rep["report"]["requests"] == 8
    assert [r["device_id"] for r in rep["replicas"]] == [0]


def test_serve_cli_parity_mismatch_returns_one(monkeypatch, capsys):
    """A padded-bucket MISMATCH used to be printed and forgotten."""
    from parallel_cnn_tpu import cli

    monkeypatch.setattr(
        cli, "_padded_bucket_parity",
        lambda *a, **k: {"n": 3, "bucket": 4, "max_abs_diff": 1e-3},
    )
    assert _serve_cli(monkeypatch) == 1
    assert "MISMATCH" in capsys.readouterr().out


def test_serve_cli_all_requests_failed_returns_one(monkeypatch, capsys):
    """The batcher turns a device exception into failed futures; a run
    whose requests all failed used to exit 0."""
    from parallel_cnn_tpu import cli
    from parallel_cnn_tpu.serve import engine as engine_mod

    monkeypatch.setattr(
        cli, "_padded_bucket_parity",
        lambda *a, **k: {"n": 3, "bucket": 4, "max_abs_diff": 0.0},
    )

    def device_lost(self, x):
        raise RuntimeError("device lost (injected)")

    monkeypatch.setattr(engine_mod.Engine, "predict", device_lost)
    assert _serve_cli(monkeypatch) == 1
    assert "FAILED: 8/8 requests raised" in capsys.readouterr().out
