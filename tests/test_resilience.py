"""Fault-tolerance suite: retry/backoff, sentinel policies, rollback,
checkpoint failure modes, preemption, and the deterministic chaos harness
(resilience/ — every recovery path proven end-to-end, not assumed).

Fast fault-injection tests carry the ``chaos`` marker and run in tier-1;
the subprocess kill-and-resume tests are additionally ``slow``.
"""

import dataclasses
import importlib
import logging
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_cnn_tpu.config import (
    Config,
    DataConfig,
    ResilienceConfig,
    TrainConfig,
)
from parallel_cnn_tpu.models import lenet_ref
from parallel_cnn_tpu.resilience import (
    ChaosMonkey,
    CheckpointRing,
    DivergenceError,
    PreemptionGuard,
    RetriesExhaustedError,
    RetryPolicy,
    RollbackController,
    Sentinel,
    preempt,
    retry_call,
    tree_all_finite,
)
from parallel_cnn_tpu.resilience import chaos as chaos_lib
from parallel_cnn_tpu.train import checkpoint


# ---------------------------------------------------------------- retry


def test_retry_policy_delays_deterministic():
    p = RetryPolicy(attempts=4, base_delay=1.0, max_delay=3.0, seed=7)
    a, b = list(p.delays()), list(p.delays())
    assert a == b  # pure function of the policy
    assert len(a) == 3
    # capped exponential envelope, jitter within ±50%
    for k, d in enumerate(a):
        nominal = min(1.0 * 2.0**k, 3.0)
        assert 0.5 * nominal <= d <= 1.5 * nominal
    # a different seed draws a different (still deterministic) sequence
    assert list(RetryPolicy(attempts=4, seed=8).delays()) != a


def test_retry_policy_validation():
    with pytest.raises(ValueError):
        RetryPolicy(attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(jitter=1.0)
    with pytest.raises(ValueError):
        RetryPolicy(multiplier=0.5)


def test_retry_call_bounded_and_final_error_propagates():
    calls, slept = [], []

    def flaky():
        calls.append(1)
        raise OSError("transient")

    with pytest.raises(OSError, match="transient"):
        retry_call(
            flaky,
            policy=RetryPolicy(attempts=3, seed=1),
            retry_on=(OSError,),
            sleep=slept.append,
        )
    assert len(calls) == 3  # hard bound, no infinite loop
    assert slept == list(RetryPolicy(attempts=3, seed=1).delays())


def test_retry_call_succeeds_after_transient_failures():
    state = {"n": 0}

    def eventually():
        state["n"] += 1
        if state["n"] < 3:
            raise OSError("not yet")
        return "ok"

    out = retry_call(
        eventually,
        policy=RetryPolicy(attempts=5),
        retry_on=(OSError,),
        sleep=lambda d: None,
    )
    assert out == "ok" and state["n"] == 3


def test_retry_call_does_not_catch_unlisted_errors():
    def bad():
        raise TypeError("programming error")

    calls = []
    with pytest.raises(TypeError):
        retry_call(
            bad, policy=RetryPolicy(attempts=5), retry_on=(OSError,),
            sleep=calls.append,
        )
    assert calls == []  # failed on the first attempt, no retries


# -------------------------------------------------------------- sentinel


def test_sentinel_verdicts():
    s = Sentinel()
    assert s.check(loss=0.5)
    v = s.check(loss=float("nan"))
    assert not v and "loss" in v.reason
    assert not s.check(loss=float("inf"))
    good = {"w": jnp.ones((3,)), "step": jnp.int32(7)}
    bad = {"w": jnp.array([1.0, jnp.nan]), "step": jnp.int32(7)}
    assert s.check(loss=0.1, params=good)
    v = s.check(loss=0.1, params=bad)
    assert not v and "params" in v.reason
    assert not s.check(grads=bad)


def test_tree_all_finite_skips_integer_leaves():
    assert bool(tree_all_finite({"count": jnp.int32(3)}))
    assert bool(tree_all_finite({}))  # empty tree is healthy
    assert not bool(tree_all_finite({"x": jnp.float32(jnp.inf)}))


# ------------------------------------------------- checkpoint failure modes


def _save_lenet(path, epoch=1):
    params = lenet_ref.init(jax.random.key(0))
    checkpoint.save(
        str(path), params, checkpoint.TrainState(epoch=epoch)
    )
    return params


def test_restore_truncated_checkpoint_raises_valueerror(tmp_path):
    path = tmp_path / "ckpt_1.npz"
    like = _save_lenet(path)
    chaos_lib.truncate_file(str(path))
    with pytest.raises(ValueError, match="corrupted or unreadable"):
        checkpoint.restore(str(path), like)


def test_restore_corrupted_checkpoint_raises_valueerror(tmp_path):
    path = tmp_path / "ckpt_1.npz"
    like = _save_lenet(path)
    chaos_lib.corrupt_file(str(path))
    with pytest.raises(ValueError):
        checkpoint.restore(str(path), like)


def test_restore_version_mismatch_raises(tmp_path):
    path = tmp_path / "ckpt_1.npz"
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(checkpoint, "FORMAT_VERSION", 99)
        like = _save_lenet(path)
    with pytest.raises(ValueError, match="version"):
        checkpoint.restore(str(path), like)


def test_latest_skips_torn_tmp_files(tmp_path):
    _save_lenet(tmp_path / "ckpt_2.npz", epoch=2)
    # mkstemp-style leftover of an interrupted atomic write
    (tmp_path / "tmpabc123.tmp.npz").write_bytes(b"torn")
    (tmp_path / "ckpt_9.tmp.npz").write_bytes(b"torn")
    assert checkpoint.latest(str(tmp_path)).endswith("ckpt_2.npz")


# ------------------------------------------------------ ring + rollback


def test_checkpoint_ring_prunes_to_keep(tmp_path):
    params = lenet_ref.init(jax.random.key(0))
    ring = CheckpointRing(str(tmp_path), keep=2)
    for e in range(1, 6):
        ring.save(e, params, checkpoint.TrainState(epoch=e))
    assert ring.tags() == [5, 4]
    assert checkpoint.latest(str(tmp_path)).endswith("ckpt_5.npz")


def test_checkpoint_ring_keep_zero_is_unbounded(tmp_path):
    params = lenet_ref.init(jax.random.key(0))
    ring = CheckpointRing(str(tmp_path), keep=0)
    for e in range(1, 5):
        ring.save(e, params, checkpoint.TrainState(epoch=e))
    assert ring.tags() == [4, 3, 2, 1]


def test_checkpoint_ring_restore_skips_corrupt_newest(tmp_path, caplog):
    params = lenet_ref.init(jax.random.key(1))
    ring = CheckpointRing(str(tmp_path), keep=3)
    ring.save(1, params, checkpoint.TrainState(epoch=1))
    ring.save(2, params, checkpoint.TrainState(epoch=2))
    chaos_lib.corrupt_file(ring.path_for(2))
    like = lenet_ref.init(jax.random.key(2))
    with caplog.at_level(logging.WARNING, "parallel_cnn_tpu.resilience"):
        restored = ring.restore_latest(like)
    assert restored is not None
    rparams, state, path = restored
    assert state.epoch == 1 and path.endswith("ckpt_1.npz")
    for a, b in zip(
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(rparams),
        strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert any("skipping unusable" in r.getMessage() for r in caplog.records)


def test_rollback_controller_bounded():
    c = RollbackController(max_rollbacks=2, lr_backoff=0.5)
    state = {"w": jnp.ones((2,))}
    c.commit(state)
    for expected_scale in (0.5, 0.25):
        restored, _ = c.rollback(reason="test")
        np.testing.assert_array_equal(np.asarray(restored["w"]), 1.0)
        assert c.lr_scale == expected_scale
    with pytest.raises(RetriesExhaustedError, match="max_rollbacks=2"):
        c.rollback(reason="test")


def test_rollback_controller_nothing_to_restore():
    c = RollbackController(max_rollbacks=3)
    with pytest.raises(RetriesExhaustedError, match="nothing to roll back"):
        c.rollback(reason="no commit ever happened")


def test_rollback_controller_falls_through_to_ring(tmp_path):
    params = lenet_ref.init(jax.random.key(3))
    ring = CheckpointRing(str(tmp_path), keep=2)
    ring.save(4, params, checkpoint.TrainState(epoch=4))
    c = RollbackController(max_rollbacks=1, ring=ring)  # no in-memory commit
    like = lenet_ref.init(jax.random.key(4))
    restored, state = c.rollback(like=like, reason="cross-process")
    assert state.epoch == 4
    for a, b in zip(
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(restored),
        strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------- chaos harness


def test_chaos_spec_parsing():
    m = ChaosMonkey.from_spec("nan@3")
    assert m.nan_step == 3 and m.kill_epoch is None
    m = ChaosMonkey.from_spec("kill@2")
    assert m.kill_epoch == 2 and m.kill_signal == signal.SIGTERM
    m = ChaosMonkey.from_spec("kill9@1")
    assert m.kill_epoch == 1 and m.kill_signal == signal.SIGKILL
    for bad in ("nan", "nan@", "nan@x", "boom@1"):
        with pytest.raises(ValueError):
            ChaosMonkey.from_spec(bad)


def test_poison_tree_spares_integer_leaves():
    tree = {"w": jnp.ones((2, 2)), "step": jnp.int32(5)}
    poisoned = chaos_lib.poison_tree(tree)
    assert np.isnan(np.asarray(poisoned["w"])).all()
    assert int(poisoned["step"]) == 5


def test_chaos_nan_is_one_shot():
    m = ChaosMonkey(nan_step=1)
    t = {"w": jnp.ones(())}
    t0, _ = m.after_step(t, 0.1)
    assert not np.isnan(np.asarray(t0["w"]))
    t1, _ = m.after_step(t, 0.1)
    assert np.isnan(np.asarray(t1["w"]))
    t2, _ = m.after_step(t, 0.1)  # never fires again
    assert not np.isnan(np.asarray(t2["w"]))


def test_hidden_native_lib_blocks_import_and_restores():
    modname = "parallel_cnn_tpu.data.native"
    with chaos_lib.hidden_native_lib():
        assert os.environ.get("PCNN_DISABLE_NATIVE") == "1"
        with pytest.raises(ImportError, match="PCNN_DISABLE_NATIVE"):
            importlib.import_module(modname)
    assert os.environ.get("PCNN_DISABLE_NATIVE") != "1"
    importlib.import_module(modname)  # importable again (or a clean retry)


# ------------------------------------------------------------- preemption


def test_preempt_flag_set_by_sigterm_and_reset():
    preempt.reset()
    try:
        with PreemptionGuard() as guard:
            assert guard.installed
            assert not preempt.requested()
            os.kill(os.getpid(), signal.SIGTERM)
            assert preempt.requested()  # flag only; process survives
        assert guard.preempted
    finally:
        preempt.reset()
        preempt.uninstall()
    assert not preempt.requested()


# ----------------------------------------------- end-to-end fault injection


def _lenet_cfg(**res_kw):
    return Config(
        data=DataConfig(
            loader="synthetic",
            synthetic_train_count=64,
            synthetic_test_count=16,
        ),
        train=TrainConfig(epochs=3, batch_size=16, shuffle=True),
        resilience=ResilienceConfig(**res_kw),
    )


def _load_synth(cfg):
    from parallel_cnn_tpu.data import pipeline

    train_ds, _ = pipeline.load_train_test(cfg.data)
    return train_ds


@pytest.mark.chaos
def test_nan_chaos_triggers_rollback_and_training_completes():
    from parallel_cnn_tpu.train import trainer

    cfg = _lenet_cfg(policy="rollback", max_rollbacks=2)
    result = trainer.learn(
        cfg, _load_synth(cfg), verbose=False, chaos=ChaosMonkey(nan_step=1)
    )
    assert result.rollbacks >= 1
    assert len(result.epoch_errors) == 3  # the poisoned epoch was retried
    assert all(np.isfinite(e) for e in result.epoch_errors)
    assert bool(tree_all_finite(result.params))


@pytest.mark.chaos
def test_nan_chaos_raise_policy_fails_fast():
    from parallel_cnn_tpu.train import trainer

    cfg = _lenet_cfg(policy="raise")
    with pytest.raises(DivergenceError, match="non-finite"):
        trainer.learn(
            cfg, _load_synth(cfg), verbose=False,
            chaos=ChaosMonkey(nan_step=0),
        )


@pytest.mark.chaos
def test_nan_chaos_skip_policy_discards_epoch():
    from parallel_cnn_tpu.train import trainer

    cfg = _lenet_cfg(policy="skip")
    result = trainer.learn(
        cfg, _load_synth(cfg), verbose=False, chaos=ChaosMonkey(nan_step=0)
    )
    # epoch 0's update was discarded: only the 2 healthy epochs recorded
    assert len(result.epoch_errors) == 2
    assert all(np.isfinite(e) for e in result.epoch_errors)
    assert bool(tree_all_finite(result.params))


@pytest.mark.chaos
def test_rollback_exhaustion_raises():
    """Every epoch poisoned (max_rollbacks=1) → RetriesExhaustedError."""
    from parallel_cnn_tpu.train import trainer

    class AlwaysNaN(ChaosMonkey):
        def after_step(self, tree, loss):
            self.steps_seen += 1
            return chaos_lib.poison_tree(tree), loss

    cfg = _lenet_cfg(policy="rollback", max_rollbacks=1)
    with pytest.raises(RetriesExhaustedError):
        trainer.learn(
            cfg, _load_synth(cfg), verbose=False, chaos=AlwaysNaN()
        )


@pytest.mark.chaos
def test_zoo_per_step_sentinel_rollback():
    from parallel_cnn_tpu.data import synthetic
    from parallel_cnn_tpu.nn import cifar
    from parallel_cnn_tpu.train import zoo

    imgs, labels = synthetic.make_image_dataset(64, seed=0)
    state, losses = zoo.train(
        cifar.cifar_cnn(),
        imgs,
        labels,
        in_shape=cifar.IN_SHAPE,
        epochs=1,
        batch_size=32,
        seed=0,
        verbose=False,
        resilience=ResilienceConfig(
            policy="rollback", max_rollbacks=2, check_every_steps=1
        ),
        chaos=ChaosMonkey(nan_step=0),
    )
    assert len(losses) == 1 and np.isfinite(losses[0])
    assert bool(tree_all_finite(state.params))


@pytest.mark.chaos
def test_preempt_then_resume_is_bit_exact():
    """SIGTERM after epoch 1 + epoch_offset resume == uninterrupted run."""
    from parallel_cnn_tpu.train import trainer

    cfg = _lenet_cfg(policy="off")
    train_ds = _load_synth(cfg)
    p0 = lenet_ref.init(jax.random.key(cfg.train.seed))

    continuous = trainer.learn(cfg, train_ds, params=p0, verbose=False)
    assert len(continuous.epoch_errors) == 3

    preempt.reset()
    try:
        with PreemptionGuard():
            part1 = trainer.learn(
                cfg, train_ds, params=p0, verbose=False,
                chaos=ChaosMonkey(kill_epoch=1),
            )
        assert part1.preempted and len(part1.epoch_errors) == 1
    finally:
        preempt.reset()
        preempt.uninstall()

    cfg2 = cfg.replace(
        train=dataclasses.replace(cfg.train, epochs=2)
    )
    part2 = trainer.learn(
        cfg2, train_ds, params=part1.params, verbose=False, epoch_offset=1
    )
    assert part1.epoch_errors + part2.epoch_errors == continuous.epoch_errors
    for a, b in zip(
        jax.tree_util.tree_leaves(continuous.params),
        jax.tree_util.tree_leaves(part2.params),
        strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.chaos
def test_pallas_failure_fails_the_run(monkeypatch):
    """A Pallas kernel-path failure is the run's failure: asking for
    --ops pallas must never finish on the XLA path with exit 0."""
    from parallel_cnn_tpu.ops import pallas as pk
    from parallel_cnn_tpu.train import trainer

    def boom(*a, **k):
        raise RuntimeError("mosaic compile failed (injected)")

    monkeypatch.setattr(pk, "batched_value_and_ref_grads", boom)
    cfg = Config(
        data=DataConfig(
            loader="synthetic",
            synthetic_train_count=48,
            synthetic_test_count=16,
        ),
        # dt differs from other tests so a previously compiled pallas step
        # can't be served from the jit cache without hitting the patch.
        train=TrainConfig(
            epochs=1, batch_size=12, ops="pallas", dt=1.25e-2
        ),
        resilience=ResilienceConfig(policy="off"),
    )
    with pytest.raises(RuntimeError, match="mosaic compile failed"):
        trainer.learn(cfg, _load_synth(cfg), verbose=False)


# ------------------------------------------- subprocess kill-and-resume


def _run_cli(args, timeout=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # see tests/test_aux.py._run_cli
    return subprocess.run(
        [sys.executable, "-m", "parallel_cnn_tpu", *args],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )


_CLI_BASE = [
    "--loader", "synthetic",
    "--synthetic-train-count", "64",
    "--synthetic-test-count", "16",
    "--epochs", "3",
    "--batch-size", "16",
    "--seed", "3",
    "--shuffle",
]


def _final_ckpt_arrays(path):
    with np.load(path) as z:
        return {k: np.array(z[k]) for k in z.files if k != "__meta__"}


@pytest.mark.slow
@pytest.mark.chaos
def test_cli_sigterm_chaos_then_resume_matches_uninterrupted(tmp_path):
    """--chaos kill@1 SIGTERMs the run after epoch 1's checkpoint; --resume
    must land on the SAME final params as an uninterrupted run (the strict
    determinism contract: per-epoch seeds derive from the global epoch)."""
    full, cut = str(tmp_path / "full"), str(tmp_path / "cut")

    r = _run_cli(_CLI_BASE + ["--checkpoint-dir", full])
    assert r.returncode == 0, r.stderr

    r = _run_cli(_CLI_BASE + ["--checkpoint-dir", cut, "--chaos", "kill@1"])
    assert r.returncode == 0, r.stderr  # graceful preemption exit
    assert "preempted" in r.stdout
    assert os.path.exists(os.path.join(cut, "ckpt_1.npz"))
    assert not os.path.exists(os.path.join(cut, "ckpt_2.npz"))

    r = _run_cli(_CLI_BASE + ["--checkpoint-dir", cut, "--resume"])
    assert r.returncode == 0, r.stderr
    assert "resumed from" in r.stdout

    a = _final_ckpt_arrays(os.path.join(full, "ckpt_3.npz"))
    b = _final_ckpt_arrays(os.path.join(cut, "ckpt_3.npz"))
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.slow
@pytest.mark.chaos
def test_cli_sigkill_chaos_leaves_resumable_state(tmp_path):
    """kill9@1 is an unannounced hard kill — the atomic per-epoch
    checkpoint must still leave a resumable, trajectory-exact state."""
    full, cut = str(tmp_path / "full"), str(tmp_path / "cut")

    r = _run_cli(_CLI_BASE + ["--checkpoint-dir", full])
    assert r.returncode == 0, r.stderr

    r = _run_cli(_CLI_BASE + ["--checkpoint-dir", cut, "--chaos", "kill9@1"])
    assert r.returncode == -signal.SIGKILL
    assert os.path.exists(os.path.join(cut, "ckpt_1.npz"))

    r = _run_cli(_CLI_BASE + ["--checkpoint-dir", cut, "--resume"])
    assert r.returncode == 0, r.stderr

    a = _final_ckpt_arrays(os.path.join(full, "ckpt_3.npz"))
    b = _final_ckpt_arrays(os.path.join(cut, "ckpt_3.npz"))
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
