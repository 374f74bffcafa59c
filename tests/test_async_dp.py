"""Straggler-tolerant async data parallelism (train/async_dp.py).

Covers the bounded-staleness server (ledger enforcement, stale-0 ≡ sync
bit-exactness, the hard barrier under a chaos straggler), EASGD elastic
averaging (center convergence, the sharded ring round vs the host pull),
the `slow-worker@STEP:MS` chaos hook and its shared grammar constant,
sentinel composition (a NaN on one worker never poisons the
server/center), obs journal conservation for the new event kinds, the
AsyncConfig env/flag surface, and the per-rank decorrelated retry jitter
(satellite b).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_cnn_tpu.config import AsyncConfig
from parallel_cnn_tpu.models import lenet_ref
from parallel_cnn_tpu.resilience.chaos import (
    SPEC_KINDS, ChaosMonkey,
)
from parallel_cnn_tpu.resilience.retry import RetryPolicy
from parallel_cnn_tpu.resilience.sentinel import Sentinel
from parallel_cnn_tpu.train import async_dp

pytestmark = pytest.mark.async_dp

W, B = 4, 8
DT, STEP_MS, HORIZON = 0.05, 100.0, 1600.0


@pytest.fixture(scope="module")
def params():
    return lenet_ref.init(jax.random.key(7))


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.uniform(0, 1, (W, B, 28, 28)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 10, (W, B)).astype(np.int32))
    return xs, ys


def _run(params, data, cfg, **kw):
    xs, ys = data
    kw.setdefault("dt", DT)
    kw.setdefault("step_ms", STEP_MS)
    return async_dp.run_async(params, xs, ys, cfg=cfg, **kw)


# ---------------------------------------------------------------------------
# Staleness ledger
# ---------------------------------------------------------------------------


def test_ledger_records_within_bound():
    led = async_dp.StalenessLedger(workers=2, bound=2)
    led.record(0, 0)
    led.record(0, 2)
    led.record(1, 1)
    assert led.max_staleness() == 2
    assert led.total_applied() == 3
    assert led.entries == [[0, 2], [1]]


def test_ledger_raises_past_bound():
    led = async_dp.StalenessLedger(workers=1, bound=1)
    with pytest.raises(RuntimeError, match="staleness bound violated"):
        led.record(0, 2)
    with pytest.raises(RuntimeError, match="staleness bound violated"):
        led.record(0, -1)


def test_ledger_never_exceeds_bound_under_chaos(params, data):
    """Every APPLIED contribution — not just the max — stays ≤ S, clean
    and under the 400 ms straggler, and the chaos run genuinely used the
    slack (max staleness > 0, i.e. the run was not secretly synchronous).
    """
    cfg = AsyncConfig(mode="stale", staleness_bound=2, workers=W)
    for chaos in (None, ChaosMonkey.from_spec("slow-worker@2:400")):
        res = _run(params, data, cfg, horizon_ms=HORIZON, chaos=chaos)
        for worker_entries in res.ledger.entries:
            assert all(0 <= s <= 2 for s in worker_entries)
    assert res.ledger.max_staleness() > 0  # the chaos run went async


# ---------------------------------------------------------------------------
# Parity: stale-0 ≡ sync, bounded loss delta for S > 0
# ---------------------------------------------------------------------------


def test_stale0_bit_exact_vs_sync(params, data):
    sync = _run(params, data, AsyncConfig(mode="off", workers=W),
                max_server_steps=3)
    s0 = _run(params, data,
              AsyncConfig(mode="stale", staleness_bound=0, workers=W),
              max_server_steps=3)
    assert sync.losses == s0.losses
    for a, b in zip(jax.tree_util.tree_leaves(sync.params),
                    jax.tree_util.tree_leaves(s0.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_stale_chaos_loss_delta_bounded(params, data):
    """The async contract: NOT bitwise parity, a seeded 3-step
    |loss − sync| ≤ 1e-2 instead — clean and under the straggler."""
    xs, ys = data
    ex, ey = xs.reshape(W * B, 28, 28), ys.reshape(W * B)
    sync = _run(params, data, AsyncConfig(mode="off", workers=W),
                max_server_steps=3)
    base = float(async_dp.eval_err(sync.params, ex, ey))
    cfg = AsyncConfig(mode="stale", staleness_bound=2, workers=W)
    for chaos in (None, ChaosMonkey.from_spec("slow-worker@2:400")):
        res = _run(params, data, cfg, max_server_steps=3, chaos=chaos)
        delta = abs(base - float(async_dp.eval_err(res.params, ex, ey)))
        assert delta <= 1e-2, f"chaos={chaos}: |dloss|={delta:.3e}"


# ---------------------------------------------------------------------------
# Throughput under the straggler — the both-ways gate
# ---------------------------------------------------------------------------


def test_straggler_throughput_both_ways(params, data):
    """Sync ring degrades below 0.8x clean under slow-worker@2:400
    (anti-vacuity); stale-2 and EASGD both hold ≥ 0.8x."""
    ratios = {}
    for name, cfg in {
        "sync": AsyncConfig(mode="off", workers=W),
        "stale": AsyncConfig(mode="stale", staleness_bound=2, workers=W),
        "easgd": AsyncConfig(mode="easgd", easgd_period=4, easgd_rho=0.5,
                             workers=W),
    }.items():
        clean = _run(params, data, cfg, horizon_ms=HORIZON)
        chaos = _run(params, data, cfg, horizon_ms=HORIZON,
                     chaos=ChaosMonkey.from_spec("slow-worker@2:400"))
        ratios[name] = chaos.throughput() / clean.throughput()
    assert ratios["sync"] < 0.8, ratios
    assert ratios["stale"] >= 0.8, ratios
    assert ratios["easgd"] >= 0.8, ratios


def test_virtual_clock_is_deterministic(params, data):
    """Two identical chaos runs produce identical schedules and params —
    no wall clock, no unseeded randomness anywhere in the harness."""
    cfg = AsyncConfig(mode="stale", staleness_bound=2, workers=W)
    runs = [
        _run(params, data, cfg, horizon_ms=HORIZON,
             chaos=ChaosMonkey.from_spec("slow-worker@2:400"))
        for _ in range(2)
    ]
    assert runs[0].virtual_ms == runs[1].virtual_ms
    assert runs[0].microbatches == runs[1].microbatches
    assert runs[0].losses == runs[1].losses
    for a, b in zip(jax.tree_util.tree_leaves(runs[0].params),
                    jax.tree_util.tree_leaves(runs[1].params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# EASGD
# ---------------------------------------------------------------------------


def test_easgd_center_learns(params, data):
    """The elastic-averaged center improves on the training batch —
    local SGD plus ρ-pulls genuinely train, they don't just average
    noise."""
    xs, ys = data
    ex, ey = xs.reshape(W * B, 28, 28), ys.reshape(W * B)
    cfg = AsyncConfig(mode="easgd", easgd_period=1, easgd_rho=0.9,
                      workers=W)
    res = _run(params, data, cfg, max_server_steps=6)
    before = float(async_dp.eval_err(params, ex, ey))
    after = float(async_dp.eval_err(res.params, ex, ey))
    assert after < before
    assert res.easgd_rounds == 6 * W  # period 1: one round per local step


def test_easgd_round_sharded_matches_host(host_devices):
    """The device-resident ring round (train.easgd_round graftcheck
    entry) computes the same update as the host-side reference math."""
    from jax.sharding import PartitionSpec as P

    from parallel_cnn_tpu.config import MeshConfig
    from parallel_cnn_tpu.parallel import mesh as mesh_lib

    n, shard_len, rho = 8, 16, 0.5
    mesh = mesh_lib.make_mesh(MeshConfig(data=n, model=1),
                              devices=host_devices[:n])
    rng = np.random.default_rng(3)
    wf = rng.normal(size=(n, n * shard_len)).astype(np.float32)
    cs = rng.normal(size=(n, shard_len)).astype(np.float32)

    def body(w, c):
        nw, nc = async_dp.easgd_round_sharded(
            w[0], c[0], jnp.float32(rho), axis_name="data", axis_size=n
        )
        return nw[None], nc[None]

    f = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data", None), P("data", None)),
        out_specs=(P("data", None), P("data", None)), check_vma=False,
    ))
    nw, nc = f(jnp.asarray(wf), jnp.asarray(cs))

    center = cs.reshape(-1)
    delta = rho * (wf - center[None, :])
    np.testing.assert_allclose(np.asarray(nw), wf - delta,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(nc).reshape(-1), center + np.mean(delta, axis=0),
        rtol=1e-5, atol=1e-5,
    )


# ---------------------------------------------------------------------------
# Chaos: slow-worker hook + the shared grammar constant (satellite a)
# ---------------------------------------------------------------------------


def test_slow_worker_spec_parses():
    m = ChaosMonkey.from_spec("slow-worker@2:400")
    assert m.slow_worker == (2, 400.0)
    assert not m.slow_worker_fired


def test_slow_worker_hook_is_one_shot():
    m = ChaosMonkey.from_spec("slow-worker@3:250")
    assert m.slow_worker_at(0) is None
    assert m.slow_worker_at(2) is None
    assert m.slow_worker_at(3) == 250.0
    assert m.slow_worker_fired
    assert m.slow_worker_at(3) is None  # fired exactly once
    assert m.slow_worker_at(99) is None


def test_slow_worker_fires_late_if_step_skipped():
    """step >= N semantics: a worker that never dispatches exactly N
    still gets the stall on its next dispatch."""
    m = ChaosMonkey.from_spec("slow-worker@3:250")
    assert m.slow_worker_at(5) == 250.0


@pytest.mark.parametrize("spec", [
    "slow-worker@2", "slow-worker@2:", "slow-worker@2:0",
    "slow-worker@2:-5", "slow-worker@x:100",
])
def test_slow_worker_grammar_rejects(spec):
    with pytest.raises(ValueError, match="slow-worker wants"):
        ChaosMonkey.from_spec(spec)


def test_grammar_error_names_every_spec_kind():
    """The single _GRAMMAR constant (both raise sites share it) names
    every registered spec kind — a new kind that forgets to register in
    SPEC_KINDS fails here."""
    with pytest.raises(ValueError) as ei:
        ChaosMonkey.from_spec("definitely-not-a-spec")
    msg = str(ei.value)
    assert len(SPEC_KINDS) >= 7
    for kind in SPEC_KINDS:
        assert kind in msg, f"grammar error omits {kind!r}: {msg}"


# ---------------------------------------------------------------------------
# Sentinel composition: NaN on one worker never poisons server/center
# ---------------------------------------------------------------------------


def _all_finite(tree):
    return all(bool(jnp.all(jnp.isfinite(leaf)))
               for leaf in jax.tree_util.tree_leaves(tree))


def test_nan_worker_dropped_stale(params, data):
    cfg = AsyncConfig(mode="stale", staleness_bound=2, workers=W)
    res = _run(params, data, cfg, max_server_steps=3,
               chaos=ChaosMonkey(nan_step=1), sentinel=Sentinel())
    assert res.dropped == 1
    assert _all_finite(res.params)
    assert all(np.isfinite(l) for l in res.losses)


def test_nan_worker_reset_from_center_easgd(params, data):
    cfg = AsyncConfig(mode="easgd", easgd_period=2, easgd_rho=0.5,
                      workers=W)
    res = _run(params, data, cfg, max_server_steps=4,
               chaos=ChaosMonkey(nan_step=1), sentinel=Sentinel())
    assert res.dropped == 1
    assert _all_finite(res.params)


def test_nan_without_sentinel_poisons(params, data):
    """Anti-vacuity for the two tests above: without the sentinel the
    same injection DOES reach the server params."""
    cfg = AsyncConfig(mode="stale", staleness_bound=2, workers=W)
    res = _run(params, data, cfg, max_server_steps=3,
               chaos=ChaosMonkey(nan_step=1), sentinel=None)
    assert res.dropped == 0
    assert not _all_finite(res.params)


# ---------------------------------------------------------------------------
# Obs journal events
# ---------------------------------------------------------------------------


def _bundle(tmp_path, run):
    from parallel_cnn_tpu import obs as obs_lib
    from parallel_cnn_tpu.config import ObsConfig

    return obs_lib.from_config(
        ObsConfig(trace=True, dir=str(tmp_path), jax_annotations=False),
        run=run,
    )


def test_obs_events_stale(params, data, tmp_path):
    bundle = _bundle(tmp_path, "stale")
    cfg = AsyncConfig(mode="stale", staleness_bound=2, workers=W)
    res = _run(params, data, cfg, horizon_ms=HORIZON,
               chaos=ChaosMonkey.from_spec("slow-worker@2:400"),
               obs=bundle)
    counts = bundle.journal.counts()
    bundle.finish()
    assert counts.get("chaos_slow_worker", 0) == 1
    assert counts.get("straggler_detected", 0) == res.stragglers >= 1
    # One `staleness` event per applied optimizer step plus one per
    # barrier hold — at least the step count.
    assert counts.get("staleness", 0) >= res.server_steps


def test_obs_events_easgd(params, data, tmp_path):
    bundle = _bundle(tmp_path, "easgd")
    cfg = AsyncConfig(mode="easgd", easgd_period=2, easgd_rho=0.5,
                      workers=W)
    res = _run(params, data, cfg, max_server_steps=4, obs=bundle)
    counts = bundle.journal.counts()
    spans = [e for e in bundle.tracer.events()
             if e.get("name") == "train.easgd_round"]
    bundle.finish()
    assert counts.get("easgd_round", 0) == res.easgd_rounds == 2 * W
    assert len(spans) == res.easgd_rounds  # span brackets every round


def test_nan_drop_is_journaled(params, data, tmp_path):
    bundle = _bundle(tmp_path, "drop")
    cfg = AsyncConfig(mode="stale", staleness_bound=2, workers=W)
    res = _run(params, data, cfg, max_server_steps=3,
               chaos=ChaosMonkey(nan_step=1), sentinel=Sentinel(),
               obs=bundle)
    counts = bundle.journal.counts()
    bundle.finish()
    assert counts.get("sentinel_drop", 0) == res.dropped == 1


# ---------------------------------------------------------------------------
# Config surface
# ---------------------------------------------------------------------------


def test_async_config_validation():
    with pytest.raises(ValueError, match="mode"):
        AsyncConfig(mode="bogus")
    with pytest.raises(ValueError, match="staleness_bound"):
        AsyncConfig(staleness_bound=-1)
    with pytest.raises(ValueError, match="easgd_period"):
        AsyncConfig(easgd_period=0)
    with pytest.raises(ValueError, match="easgd_rho"):
        AsyncConfig(easgd_rho=0.0)
    with pytest.raises(ValueError, match="easgd_rho"):
        AsyncConfig(easgd_rho=1.5)
    with pytest.raises(ValueError, match="workers"):
        AsyncConfig(workers=0)
    with pytest.raises(ValueError, match="straggler_factor"):
        AsyncConfig(straggler_factor=1.0)
    assert AsyncConfig().enabled
    assert not AsyncConfig(mode="off").enabled


def test_async_config_from_env(monkeypatch):
    for var in ("PCNN_ASYNC_MODE", "PCNN_ASYNC_STALENESS",
                "PCNN_ASYNC_EASGD_PERIOD", "PCNN_ASYNC_EASGD_RHO",
                "PCNN_ASYNC_WORKERS"):
        monkeypatch.delenv(var, raising=False)
    assert AsyncConfig.from_env() is None
    monkeypatch.setenv("PCNN_ASYNC_MODE", "easgd")
    monkeypatch.setenv("PCNN_ASYNC_STALENESS", "5")
    monkeypatch.setenv("PCNN_ASYNC_EASGD_PERIOD", "7")
    monkeypatch.setenv("PCNN_ASYNC_EASGD_RHO", "0.25")
    monkeypatch.setenv("PCNN_ASYNC_WORKERS", "6")
    cfg = AsyncConfig.from_env()
    assert cfg == AsyncConfig(mode="easgd", staleness_bound=5,
                              easgd_period=7, easgd_rho=0.25, workers=6)


def test_run_async_arg_validation(params, data):
    xs, ys = data
    cfg = AsyncConfig(mode="stale", workers=W)
    with pytest.raises(ValueError, match="exactly one"):
        async_dp.run_async(params, xs, ys, cfg=cfg)
    with pytest.raises(ValueError, match="exactly one"):
        async_dp.run_async(params, xs, ys, cfg=cfg,
                           horizon_ms=100.0, max_server_steps=1)
    with pytest.raises(ValueError, match="workers"):
        async_dp.run_async(
            params, xs, ys, cfg=dataclasses.replace(cfg, workers=W + 1),
            horizon_ms=100.0,
        )


# ---------------------------------------------------------------------------
# Decorrelated retry jitter (satellite b)
# ---------------------------------------------------------------------------


def test_decorrelated_is_deterministic_per_rank():
    p = RetryPolicy(attempts=4, base_delay=0.5, seed=11)
    a = list(p.decorrelated(rank=3).delays())
    b = list(p.decorrelated(rank=3).delays())
    assert a == b


def test_decorrelated_differs_across_ranks():
    p = RetryPolicy(attempts=4, base_delay=0.5, seed=11)
    seqs = [tuple(p.decorrelated(rank=r).delays()) for r in range(4)]
    assert len(set(seqs)) == 4  # no two ranks share a delay sequence


def test_decorrelated_keeps_envelope():
    p = RetryPolicy(attempts=6, base_delay=2.0, max_delay=5.0,
                    multiplier=3.0, jitter=0.4, seed=2)
    q = p.decorrelated(rank=9)
    assert (q.attempts, q.base_delay, q.max_delay, q.multiplier,
            q.jitter) == (6, 2.0, 5.0, 3.0, 0.4)
    # Every delay stays inside the jittered cap.
    assert all(d <= 5.0 * 1.4 + 1e-9 for d in q.delays())
    with pytest.raises(ValueError, match="rank"):
        p.decorrelated(rank=-1)
