"""Hold an `ouro` configuration's model, as the program loops, exits, mixes
and differentiates it, against its plain float32 reference
(benchmark/reference/ouro.py) at the published widths and the timed
sequence length, outside any timed window: `compare_afmoe.py`'s sibling,
by that file's loop (seeds, then controls on seeds of their own, report,
exit code). Not part of any run of a cell: it is what a builder runs on
the chip to read the bounds a traffic file's `check` is then given
(PERF.md section 6).

    python3 benchmark/tools/compare_ouro.py --workload ouro_loop_train \
        --seeds 16 [--seed0 2701000000] [--seed-list 4801000021,4817000003] \
        [--controls float8_e4m3fn,three_passes,...] [--control-seeds 1] \
        [--bias-only] [--out chiprun_out/cmp.json]

`--mode init` (the only mode) is the cell's own check, a row a seed
(`benchmark/runners/train_zoo_tokens_gradnorm.py:checker` with the cell's
bounds; a row carries both readings of every leaf, `check_grad_by_leaf`,
and the reference's terms). `--controls` go through the same comparison
and each has to read `correct: false` (the tool exits 1 where one reads
true):

    float8_e4m3fn        (any dtype) the reference with every matmul's
                         operands rounded through it, one precision below
                         the bf16 the configuration trains in
    three_passes         one pass fewer over the stack: 3 for 4
    grad_stopped         the gradient stopped between passes: a pass
                         learns from its own exit alone
    p_detached           the exit distribution detached where it weighs
                         the cross-entropies and the entropy's logs
    entropy_dropped      the entropy term dropped (beta = 0)
    norm_outside_loop    the final norm at the exits only: a pass hands
                         the next what its stack gave, not the normed state
    post_norms_dropped   the second norm of each sub-layer dropped
    rope_dropped         RoPE dropped
    bias_dead            the exit gate's bias takes no gradient
    bias_doubled         the exit gate's bias takes twice its gradient: the
                         one fault the cell's relative limits cannot see
                         (a length of ln 2 lies inside what clean seeds
                         read); it is here for `gate_bias`, below, and for
                         the absolute limit the runner lacks (PERF.md
                         section 7) — on the chip it reads `correct: true`

The faults are planted in the system.

Every row also carries `gate_bias` (`bias_reader`): the exit gate's bias is
ONE number whose gradient is the mean over the positions of terms of either
sign, so the check's two relative readings of it (a sign, and the log of a
ratio to a sum that nearly cancels) say little. Read apart, from the same
draws: the reference's gradient of the bias `g_ref`, the system's own
`g_sys` (its backward through the gate, bf16 trunk), the mean and the
root mean square of the per-position terms `g_ref` is the mean of, and the
root mean square of the reference's gradient of the gate's weight — the
scales an absolute limit on `|g_sys - g_ref|` would be set against.
`--bias-only` reads nothing else (two forward passes a seed: many seeds).
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402
from benchmark.runners import train_zoo_tokens_gradnorm  # noqa: E402
from benchmark.tools import compare_afmoe  # noqa: E402

FAULTS = ("three_passes", "grad_stopped", "p_detached", "entropy_dropped",
          "norm_outside_loop", "post_norms_dropped", "rope_dropped",
          "bias_dead", "bias_doubled")


def passes_handing(carried):
    """`Ouro._passes` written out again — the same scopes, the same scan —
    with `carried(what the stack gave, the final norm of it)` handed to the
    next pass where the model hands the closed state; every exit reads the
    closed state as before. (The model has no seam for a fault: the two
    faults of what a pass hands on are planted by putting this in
    `_passes`'s place, and tests/test_ouro.py holds the copy to the model.)"""
    import jax
    from jax import lax

    def _passes(self, params, x, train, at_exit):
        with jax.named_scope("embed"):
            h = self._embed().apply(params["embed"], {}, x)[0]

        def turn(h, _):
            for i, (layer, p) in enumerate(zip(
                    self._layers(), params["layers"], strict=True)):
                with jax.named_scope(f"l{i}"):
                    h, _ = self._run(layer, p, {}, h, train)
            with jax.named_scope("exit"):
                x, h = h, self._closed(params, h)
                return carried(x, h), at_exit(h)

        with jax.named_scope("ut"):
            return lax.scan(turn, h, None, length=self.passes,
                            unroll=self.passes)

    return _passes


def _with_kwargs(cfg, **kwargs):
    fac = cfg["factory"]
    return dict(cfg, factory=dict(fac, kwargs=dict(fac["kwargs"], **kwargs)))


@contextlib.contextmanager
def control(cfg, reference, name):
    """The configuration's model with the fault `name` planted, or (a
    dtype's name) the clean model against a reference rounded through
    it; everything is put back on the way out."""
    import jax.numpy as jnp
    from jax import lax
    from benchmark.reference import glm_moe as rounded
    from parallel_cnn_tpu.nn import afmoe, ouro

    saved = (ouro.Ouro._passes, ouro.exit_distribution,
             afmoe.SandwichLayer._post, afmoe.rope, ouro.Ouro._gate)
    distribution, gate = saved[1], saved[4]

    def with_bias(times):
        """`Ouro._gate` whose bias has its value and `times` its gradient."""
        def _gate(self, params, h):
            b = params["exit_gate"]["b"]
            b = times * b + lax.stop_gradient((1.0 - times) * b)
            return gate(self, dict(params, exit_gate=dict(
                params["exit_gate"], b=b)), h)
        return _gate

    try:
        if name == "three_passes":
            cfg = _with_kwargs(
                cfg, total_ut_steps=cfg["arch"]["total_ut_steps"] - 1)
        elif name == "grad_stopped":
            ouro.Ouro._passes = passes_handing(
                lambda x, h: lax.stop_gradient(h))
        elif name == "p_detached":
            def detached(a):
                p, log_p = distribution(a)
                return lax.stop_gradient(p), log_p

            ouro.exit_distribution = detached
        elif name == "entropy_dropped":
            cfg = _with_kwargs(cfg, entropy_weight=0.0)
        elif name == "norm_outside_loop":
            ouro.Ouro._passes = passes_handing(lambda x, h: x)
        elif name == "post_norms_dropped":
            afmoe.SandwichLayer._post = lambda self, gain, y: y
        elif name == "rope_dropped":
            afmoe.rope = lambda x, theta: x
        elif name == "bias_dead":
            ouro.Ouro._gate = with_bias(0.0)
        elif name == "bias_doubled":
            ouro.Ouro._gate = with_bias(2.0)
        else:
            rounded.ROUND = jnp.dtype(name)
            reference._programs.cache_clear()
        yield common.build_model(cfg)
    finally:
        (ouro.Ouro._passes, ouro.exit_distribution,
         afmoe.SandwichLayer._post, afmoe.rope, ouro.Ouro._gate) = saved
        if rounded.ROUND is not None:
            rounded.ROUND = None
            reference._programs.cache_clear()


def bias_reader(cfg, traffic, model, reference):
    """seed -> the exit gate's bias read apart (module docstring), on the
    check's own draws. The bias reaches the loss through the gates' logits
    `a (T, N, S)` alone, so its gradient is the sum of the loss's gradient
    of them: the reference side is one forward pass in float32 and the
    mixture's gradient of `a`, a position's term the sum over its gates
    times the positions' count; the system side is its own backward, of
    the gate's two leaves alone (the trunk takes no tangent)."""
    import jax
    import jax.numpy as jnp
    from benchmark import token_data

    arch, chk = cfg["arch"], traffic["check"]
    length = traffic["sequence_length"]
    fresh = jax.jit(lambda key: model.init(key, (length,))[:2])

    def mixed(a, ce):
        lam = list(1.0 / (1.0 + jnp.exp(-a)))
        p = reference.exit_distribution(lam)
        expected = jnp.mean(sum(p_t * ce_t for p_t, ce_t in zip(p, ce)))
        entropy = jnp.mean(-sum(
            jnp.where(p_t > 0, p_t * jnp.log(jnp.where(p_t > 0, p_t, 1.0)), 0.0)
            for p_t in p))
        return expected - arch["entropy_weight"] * entropy

    @jax.jit
    def of_reference(params, x, y):
        params = jax.tree_util.tree_map(
            lambda v: v.astype(jnp.float32), params)
        closed = reference.passes(arch, params, x)
        ce = jnp.stack([reference.token_losses(params, h, y) for h in closed])
        g = params["exit_gate"]
        a = jnp.stack([jnp.matmul(h, g["w"])[..., 0] + g["b"][0] for h in closed])
        da = jax.grad(mixed)(a, ce)
        terms = jnp.sum(da, axis=0) * da[0].size
        w = sum(jnp.einsum("ns,nsd->d", d, h) for d, h in zip(da, closed))
        return {"g_ref": jnp.mean(terms),
                "term_mean_abs": jnp.mean(jnp.abs(terms)),
                "term_rms": jnp.sqrt(jnp.mean(terms * terms)),
                "gate_weight_rms": jnp.sqrt(jnp.mean(w * w))}

    @jax.jit
    def of_system(params, state, x, y):
        return jax.grad(lambda gate: model.loss(
            dict(params, exit_gate=gate), state, x, y)[0])(
                params["exit_gate"])["b"][0]

    def read(seed):
        x, y = token_data.synthetic_tokens(
            jax.random.fold_in(jax.random.key(seed), 1), n=chk["batch"],
            length=length, vocab=arch["vocab_size"])
        params, state = fresh(jax.random.key(seed))
        with jax.default_matmul_precision("highest"):
            out = of_reference(params, x, y)
        out = {k: float(v) for k, v in out.items()}
        out["g_sys"] = float(of_system(params, state, x, y))
        out["positions"] = int(x.size)
        return out

    return read


def init_comparer(cfg, traffic, model, reference):
    """seed -> one row: the cell's own check
    (`train_zoo_tokens_gradnorm.checker`) and what it compared, and the
    gate's bias read apart (`bias_reader`). (No rows are held on either
    side: `compare_glm_moe.init_comparer`'s widest difference of them has
    nothing to take a maximum of.)"""
    check = train_zoo_tokens_gradnorm.checker(cfg, traffic, model, reference)
    bias = bias_reader(cfg, traffic, model, reference)

    def compare(seed):
        notes = {}
        correct = check(seed, notes)
        losses = notes["check_losses"]
        return {
            "seed": seed, "correct": correct, **notes,
            "gate_bias": bias(seed),
            "loss_gaps": [abs(a / b - 1) for a, b in zip(
                losses["system"], losses["reference"])],
            "step_moves_loss": abs(
                losses["reference"][1] / losses["reference"][0] - 1)}

    return compare


def bias_comparer(cfg, traffic, model, reference):
    """seed -> one row of `--bias-only`: `bias_reader`'s and no check."""
    bias = bias_reader(cfg, traffic, model, reference)
    return lambda seed: {"seed": seed, "correct": None, "gate_bias": bias(seed)}


def main(argv=None) -> int:
    """`compare_afmoe.main`, its loop unedited, with this family's faults,
    `control` and check (`--bias-only`: `bias_comparer`) in the place of
    its own."""
    argv = list(sys.argv[1:] if argv is None else argv)
    bias_only = "--bias-only" in argv
    if bias_only:
        argv.remove("--bias-only")
    names = ("control", "FAULTS", "init_comparer")
    theirs = [getattr(compare_afmoe, n) for n in names]
    compare_afmoe.control, compare_afmoe.FAULTS = control, FAULTS
    compare_afmoe.init_comparer = bias_comparer if bias_only else init_comparer
    try:
        return compare_afmoe.main(argv)
    finally:
        for n, was in zip(names, theirs):
            setattr(compare_afmoe, n, was)


if __name__ == "__main__":
    sys.exit(main())
