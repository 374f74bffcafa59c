"""Hold a `bailing_hybrid` configuration's model, as the program builds,
scans, routes and differentiates it, against its plain float32 reference
(benchmark/reference/bailing_hybrid.py) at the published widths and the
timed sequence length, outside any timed window: `compare_afmoe.py`'s
sibling, by that file's loop (seeds, then controls on seeds of their own,
report, exit code) and the same two comparers. Not part of any run of a
cell: it is what a builder runs on the chip to read the bounds a traffic
file's `check` is then given (PERF.md section 6).

    python3 benchmark/tools/compare_bailing_hybrid.py --workload ling3f_train \
        --seeds 32 [--seed0 2701000000] [--seed-list 4301000021,4317000003] \
        [--mode init|layers] [--controls float8_e4m3fn,decay_per_head,...] \
        [--control-seeds 1] [--out chiprun_out/cmp.json]

`--mode init` (default) is the cell's own check, a row a seed
(`benchmark/runners/train_zoo_tokens_gradnorm.py:checker` with the cell's
bounds; a row carries both readings of every leaf, `check_grad_by_leaf`). `--controls` go through the same comparison and each has to read
`correct: false` (the tool exits 1 where one reads true):

    float8_e4m3fn       (any dtype) the reference with every matmul's
                        operands rounded through it
    decay_per_head      the log-decay a head (its channels' mean) for a
                        channel
    floor_dropped       the log-decay without its floor: -exp(A) softplus(z)
    beta_one            beta = 1: every write at full strength
    conv_dropped        the three short convolutions dropped
    l2_dropped          q and k not divided by their norms
    head_gate_dropped   the head-wise output gate dropped (both kinds)
    delta_after_write   (I - beta k k^T) applied after the write: with unit
                        keys, the write beta (1 - beta) k v^T
    group_limit_dropped the 8 largest of all 512 scores, no group limit
    scaling_dropped     the gates not scaled by 2.5
    absent_gates        the gates renormalised over the chosen experts this
                        chip HOLDS, the absent ones' not left in the sum
    rope_off            RoPE dropped in the full layers
    shared_dropped      the shared expert dropped

The faults are planted in the system. `--mode layers`: every parameter
leaf drawn at random; per seed, one sequence: the residual after every
layer, the loss, every leaf's gradient (`compare_glm_moe.layers_comparer`).
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402
from benchmark.runners import train_zoo_tokens_gradnorm  # noqa: E402
from benchmark.tools import compare_afmoe, compare_glm_moe  # noqa: E402

FAULTS = ("decay_per_head", "floor_dropped", "beta_one", "conv_dropped",
          "l2_dropped", "head_gate_dropped", "delta_after_write",
          "group_limit_dropped", "scaling_dropped", "absent_gates",
          "rope_off", "shared_dropped")


@contextlib.contextmanager
def control(cfg, reference, name):
    """The configuration's model with the fault `name` planted, or (a
    dtype's name) the clean model against a reference rounded through
    it; everything is put back on the way out."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import glm_moe as rounded
    from parallel_cnn_tpu.nn import bailing_hybrid as bh, glm_moe, layers
    from parallel_cnn_tpu.ops import kda

    expert = glm_moe.ExpertLayer
    saved = (bh.KDA.log_decay, kda.chunked_kda, bh.causal_conv, bh._unit,
             glm_moe._head_gated, expert._in_kept_groups, expert.route,
             glm_moe.rope, expert._shared)
    log_decay, scan, route = saved[0], saved[1], saved[6]
    try:
        if name == "decay_per_head":
            bh.KDA.log_decay = lambda self, params, z: jnp.broadcast_to(
                jnp.mean(log_decay(self, params, z), axis=-1, keepdims=True),
                z.shape)
        elif name == "floor_dropped":
            def unbounded(self, params, z):
                h, d = self.heads, self.head_dim
                z = z.astype(jnp.float32) + params["f_bias"].reshape(h, 1, d)
                return -jnp.exp(params["a_log"])[:, None, None] * jax.nn.softplus(z)

            bh.KDA.log_decay = unbounded
        elif name == "beta_one":
            kda.chunked_kda = lambda q, k, v, g, beta, *a: scan(
                q, k, v, g, jnp.ones_like(beta), *a)
        elif name == "conv_dropped":
            bh.causal_conv = lambda x, taps: x
        elif name == "l2_dropped":
            bh._unit = lambda x: x
        elif name == "head_gate_dropped":
            glm_moe._head_gated = lambda out, gate: out
        elif name == "delta_after_write":
            kda.chunked_kda = lambda q, k, v, g, beta, *a: scan(
                q, k, v * (1 - beta).astype(v.dtype)[..., None], g, beta, *a)
        elif name == "group_limit_dropped":
            expert._in_kept_groups = lambda self, biased: biased
        elif name == "scaling_dropped":
            fac = cfg["factory"]
            cfg = dict(cfg, factory=dict(fac, kwargs=dict(
                fac["kwargs"], routed_scaling_factor=1.0)))
        elif name == "absent_gates":
            def over_the_held(self, router, bias, xt, n):
                ids, gates, load, balance = route(self, router, bias, xt, n)
                here = jnp.isin(ids, jnp.asarray(self.held))
                total = jnp.sum(jnp.where(here, gates, 0), axis=1, keepdims=True)
                return (ids, self.scaling * gates / jnp.maximum(total, 1e-9),
                        load, balance)

            expert.route = over_the_held
        elif name == "rope_off":
            glm_moe.rope = lambda x, theta: x
        elif name == "shared_dropped":
            class Nothing(layers.GatedMLP):
                def apply(self, params, state, x, train=False):
                    return x * 0, state

            expert._shared = lambda self: Nothing(self.width)
        else:
            rounded.ROUND = jnp.dtype(name)
            reference._programs.cache_clear()
        yield common.build_model(cfg)
    finally:
        (bh.KDA.log_decay, kda.chunked_kda, bh.causal_conv, bh._unit,
         glm_moe._head_gated, expert._in_kept_groups, expert.route,
         glm_moe.rope, expert._shared) = saved
        if rounded.ROUND is not None:
            rounded.ROUND = None
            reference._programs.cache_clear()


def init_comparer(cfg, traffic, model, reference):
    """`compare_glm_moe.init_comparer` over this family's own check
    (`train_zoo_tokens_gradnorm.checker`)."""
    with train_zoo_tokens_gradnorm.in_place_of_theirs():
        return compare_glm_moe.init_comparer(cfg, traffic, model, reference)


def main(argv=None) -> int:
    """`compare_afmoe.main`, its loop unedited, with this family's faults,
    `control` and check in the place of its own."""
    names = ("control", "FAULTS", "init_comparer")
    theirs = [getattr(compare_afmoe, n) for n in names]
    compare_afmoe.control, compare_afmoe.FAULTS = control, FAULTS
    compare_afmoe.init_comparer = init_comparer
    try:
        return compare_afmoe.main(argv)
    finally:
        for n, was in zip(names, theirs):
            setattr(compare_afmoe, n, was)


if __name__ == "__main__":
    sys.exit(main())
