"""Hold an `sdar_moe` configuration's model, as the program builds, noises,
masks, routes and differentiates it, against its plain float32 reference
(benchmark/reference/sdar_moe.py) at the published widths and the timed
sequence length, outside any timed window: `compare_glm_moe.py`'s sibling
for the block-diffusion family, and by its two comparers. Not part of any
run of a cell: it is what a builder runs on the chip to read the bounds a
traffic file's `check` is then given (PERF.md section 6).

    python3 benchmark/tools/compare_sdar_moe.py --workload sdar_bd_train \
        --seeds 6 [--seed0 2701000000] [--mode init|layers] \
        [--controls float8_e4m3fn,causal_mask,own_clean_block,weight_dropped,\
qk_norm_off,absent_gates] [--control-seeds 1] [--out chiprun_out/cmp.json]

`--mode init` (default): the cell's own check, a row a seed — it IS
`benchmark/runners/train_zoo_tokens_bd.py:checker`, with the cell's bounds.
`--controls` go through the same comparison after the seeds, in the same
process, on seeds of their own, and each has to read `correct: false`
(the tool exits 1 where one reads true):

    float8_e4m3fn    (any dtype) the reference with every matmul's
                     operands rounded through it, one precision below the
                     bf16 the configuration trains in
    causal_mask      the mask replaced by plain causal over the 2L stream
    own_clean_block  noised queries allowed their own block's clean keys
                     (`<=` for `<`: the leak that makes the objective
                     trivial)
    weight_dropped   the 1 / t weight of the masked positions dropped
    qk_norm_off      the norms of q and k left out (at the initialisation
                     the unused leaves alone refuse it)
    absent_gates     the gates renormalised over the chosen experts this
                     chip HOLDS, the 112 absent ones' not left in the sum

The faults are planted in the system (the two mask faults run the
attention in plain XLA over all keys, a block of queries at a time, under
the faulty mask: the kernels take the true mask's structure alone).

`--mode layers`: every parameter leaf drawn at random; per seed, one
sequence: the stream's residual after every layer, the loss, every leaf's
gradient (`compare_glm_moe.layers_comparer`, handed the noised stream).
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402
from benchmark.runners import train_zoo_tokens_bd  # noqa: E402
from benchmark.tools import compare_glm_moe  # noqa: E402

# (`main` puts this module's `init_comparer` in that module's place)
_THEIR_INIT_COMPARER = compare_glm_moe.init_comparer

FAULTS = ("causal_mask", "own_clean_block", "weight_dropped", "qk_norm_off",
          "absent_gates")


def _faulty_mask(name):
    """`allowed(l, block, queries, keys)` with the fault `name`."""
    import jax.numpy as jnp

    def allowed(l, block, queries=None, keys=None):
        every = jnp.arange(2 * l)
        q = (every if queries is None else queries)[:, None]
        k = (every if keys is None else keys)[None, :]
        if name == "causal_mask":
            return k <= q
        qb, kb = (q % l) // block, (k % l) // block
        return jnp.where(q >= l, (k >= l) & (kb <= qb),
                         jnp.where(k >= l, kb <= qb, kb == qb))

    return allowed


@contextlib.contextmanager
def control(cfg, reference, name):
    """The configuration's model with the fault `name` planted, or (a
    dtype's name) the clean model against a reference rounded through
    it; everything is put back on the way out."""
    import jax.numpy as jnp
    from benchmark.reference import glm_moe as rounded
    from parallel_cnn_tpu.nn import glm_moe, sdar_moe

    gqa, expert = sdar_moe.GQA, glm_moe.ExpertLayer
    saved = (sdar_moe.allowed, gqa.core, gqa._blocks, sdar_moe._norm,
             sdar_moe.SdarMoe._cross_entropy, expert.route)
    try:
        if name in ("causal_mask", "own_clean_block"):
            sdar_moe.allowed = _faulty_mask(name)
            gqa.core = lambda self, l: ("blocks", self._q_block(l))

            def every_key(self, q, k, v):
                n, h, s, d = q.shape
                l, step = s // 2, self._q_block(s // 2)
                q = q.reshape(n, self.kv_heads, h // self.kv_heads, s, d)
                at = jnp.arange(s)
                return jnp.concatenate([
                    sdar_moe._attend(q[:, :, :, a: a + step], k, v,
                                     at[a: a + step], at, l, self.block,
                                     d ** -0.5)
                    for a in range(0, s, step)], axis=3).reshape(n, h, s, d)

            gqa._blocks = every_key
        elif name == "weight_dropped":
            sdar_moe.SdarMoe._cross_entropy = (
                lambda self, params, h, y, keep, weight=None:
                glm_moe.GlmMoe._cross_entropy(self, params, h, y, keep))
        elif name == "qk_norm_off":
            sdar_moe._norm = lambda eps, scale, x: x
        elif name == "absent_gates":
            route = expert.route

            def over_the_held(self, router, bias, xt, n):
                ids, gates, load, balance = route(self, router, bias, xt, n)
                here = jnp.isin(ids, jnp.asarray(self.held))
                total = jnp.sum(jnp.where(here, gates, 0), axis=1, keepdims=True)
                return ids, gates / jnp.maximum(total, 1e-9), load, balance

            expert.route = over_the_held
        else:
            rounded.ROUND = jnp.dtype(name)
            reference._programs.cache_clear()
        yield common.build_model(cfg)
    finally:
        (sdar_moe.allowed, gqa.core, gqa._blocks, sdar_moe._norm,
         sdar_moe.SdarMoe._cross_entropy, expert.route) = saved
        if rounded.ROUND is not None:
            rounded.ROUND = None
            reference._programs.cache_clear()


def init_comparer(cfg, traffic, model, reference):
    """`compare_glm_moe.init_comparer` over this family's own check
    (`train_zoo_tokens_bd.checker`: the unused leaves beside the losses
    and the rows)."""
    with train_zoo_tokens_bd.in_place_of_theirs():
        return _THEIR_INIT_COMPARER(cfg, traffic, model, reference)


def layers_comparer(cfg, model, reference):
    """seed -> one row: the stream's per-layer outputs, the loss and every
    leaf's gradient of one sequence at random parameter leaves."""
    import jax
    import jax.numpy as jnp
    from benchmark import token_data
    from benchmark.tools.compare_glm_moe import random_leaves
    from benchmark.tools.compare_reference import leaf_gaps

    length = cfg["input"][0]

    @jax.jit
    def leaves(seed_key, leaf_key):
        params, state, _ = model.init(seed_key, (length,))
        return random_leaves(params, state, leaf_key)[0], state

    @jax.jit
    def stream_of(state, x):
        return jnp.concatenate([model.noise(state["noise"], x)[0], x], axis=1)

    hidden_of = jax.jit(lambda p, s, x: model.hidden_states(p, s, x)[0])
    grads_of = jax.jit(jax.value_and_grad(model.loss, has_aux=True))

    def compare(seed):
        x, y = token_data.synthetic_tokens(
            jax.random.fold_in(jax.random.key(seed), 1), n=1, length=length,
            vocab=cfg["arch"]["vocab_size"])
        params, state = leaves(jax.random.key(seed), jax.random.key(seed + 1))
        stream = stream_of(state, x)
        want = reference.hidden_states(cfg["arch"], params, state, stream)
        got = hidden_of(params, state, stream)
        layer_gaps = [
            float(jnp.linalg.norm((g.astype(jnp.float32) - w).ravel())
                  / jnp.linalg.norm(w.ravel())) for g, w in zip(got, want)]
        del want, got
        (loss, _), grads = grads_of(params, state, x, y)
        ref_loss, ref_grads = reference.loss_and_grads(
            cfg["arch"], params, state, x, y)
        gaps = leaf_gaps(grads, ref_grads)
        worst = max(gaps, key=gaps.get)
        return {"seed": seed, "layer_gaps": layer_gaps,
                "loss": float(loss), "reference_loss": float(ref_loss),
                "loss_gap": abs(float(loss) / float(ref_loss) - 1),
                "worst_grad_gap": gaps[worst], "worst_leaf": worst,
                "median_grad_gap": common.median(list(gaps.values())),
                "leaves": len(gaps)}

    return compare


def main(argv=None) -> int:
    """`compare_glm_moe.main` — arguments, loop over seeds, controls on
    seeds of their own, report, exit code — with this family's faults,
    `control` and comparers where that module names its own."""
    theirs = {name: getattr(compare_glm_moe, name) for name in (
        "FAULTS", "control", "init_comparer", "layers_comparer")}
    for name in theirs:
        setattr(compare_glm_moe, name, globals()[name])
    try:
        return compare_glm_moe.main(argv)
    finally:
        for name, value in theirs.items():
            setattr(compare_glm_moe, name, value)


if __name__ == "__main__":
    sys.exit(main())
