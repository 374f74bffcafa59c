"""Hold a `keye_vl` configuration's model, as the program scores, selects,
attends and differentiates it, against its plain float32 reference
(benchmark/reference/keye_vl.py) at the published widths and the timed
sequence length, outside any timed window: `compare_afmoe.py`'s sibling, by
that file's loop (seeds, then controls on seeds of their own, report, exit
code). Not part of any run of a cell: it is what a builder runs on the chip
to read the bounds a traffic file's `check` is then given (PERF.md section
6).

    python3 benchmark/tools/compare_keye_vl.py --workload keye_dsa_train \
        --seeds 8 [--seed0 2701000000] [--seed-list 5101000021,5117000003] \
        [--controls float8_e4m3fn,window,...] [--control-seeds 1] \
        [--out chiprun_out/cmp.json]

`--mode init` (the only mode) is the cell's own check, a row a seed
(`benchmark/runners/train_zoo_tokens_gradnorm.py:checker` with the cell's
bounds; a row carries both readings of every leaf, `check_grad_by_leaf`).
`--controls` go through the same comparison and each has to read `correct:
false` (the tool exits 1 where one reads true):

    float8_e4m3fn       (any dtype) the reference with every matmul's
                        operands rounded through it, one precision below
                        the bf16 the configuration trains in
    window              the selection replaced by the last `topk` keys
    k_halved            `topk` halved
    relu_dropped        the index scores without their ReLU
    w_dropped           a plain sum over the index heads (every weight 1)
    kl_dropped          `L^I` dropped: the indexer's leaves take no gradient
    u_attached          the indexer reads the layer's input NOT detached:
                        the trunk learns from `L^I`
    p_attached          the attention's probabilities not detached in `L^I`
    not_renormalised    the softmax taken over all causal keys and the
                        unselected ones masked afterwards
    mrope_collapsed     M-RoPE's three position rows collapsed to the first
    index_rope_dropped  the indexer's RoPE dropped

The faults are planted in the system.
"""

from __future__ import annotations

import contextlib
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402
from benchmark.runners import train_zoo_tokens_gradnorm  # noqa: E402
from benchmark.tools import compare_afmoe  # noqa: E402

FAULTS = ("window", "k_halved", "relu_dropped", "w_dropped", "kl_dropped",
          "u_attached", "p_attached", "not_renormalised", "mrope_collapsed",
          "index_rope_dropped")


def _with_kwargs(cfg, **kwargs):
    fac = cfg["factory"]
    return dict(cfg, factory=dict(fac, kwargs=dict(fac["kwargs"], **kwargs)))


def _factory_topk(cfg) -> int:
    return cfg["factory"]["kwargs"].get("topk", cfg["arch"]["topk"])


@contextlib.contextmanager
def control(cfg, reference, name):
    """The configuration's model with the fault `name` planted, or (a
    dtype's name) the clean model against a reference rounded through it;
    everything is put back on the way out."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from benchmark.reference import glm_moe as rounded
    from parallel_cnn_tpu.nn import keye_vl, sdar_moe
    from parallel_cnn_tpu.ops import pallas_attention, pallas_rope

    indexer = keye_vl.Indexer
    saved = (indexer.scores, indexer.choose, keye_vl.rope, keye_vl.lax,
             keye_vl._index_kl, sdar_moe.lax, sdar_moe.GQA._chosen,
             pallas_attention.selected_attention, pallas_rope._rows)
    scores, choose = saved[0], saved[1]
    # `jax.lax` with nothing detached, for the module that is handed it
    attached = types.SimpleNamespace(**dict(vars(lax), stop_gradient=lambda x: x))

    def scored(act, weigh):
        """`Indexer.scores` with `act` for its ReLU and `weigh` on its weights."""
        def scores_(self, q, weight, k):
            with jax.named_scope("scores"):
                z = jnp.einsum("nhqd,nkd->nhqk", q, k,
                               preferred_element_type=jnp.float32)
                weight = weigh(jnp.swapaxes(weight, 1, 2).astype(jnp.float32))
                return jnp.sum(act(z) * weight[..., None], axis=1) * (
                    self.heads * self.head_dim) ** -0.5
        return scores_

    def unrenormalised(core, skip):
        """`core(..., q, k, v, bias, ...)` whose output keeps the weights a
        softmax over ALL causal keys gives the selected ones."""
        def core_(*args, **kw):
            out, lse = core(*args, **kw)
            bias = args[skip + 3]
            s = bias.shape[-1]
            every = jnp.broadcast_to(jnp.where(
                jnp.tril(jnp.ones((s, s), bool)), 0.0,
                pallas_attention.MASKED).astype(bias.dtype), bias.shape)
            _, whole = core(*args[:skip + 3], every, *args[skip + 4:], **kw)
            return out * jnp.exp(lse - whole)[..., None].astype(out.dtype), lse
        return core_

    def retrace_rope():
        # (the turn's two jits keep their traces by `positions`, which a
        # fault in what the positions MEAN does not change)
        pallas_rope.either.clear_cache()
        pallas_rope.rotate.clear_cache()

    try:
        if name == "window":
            def last_keys(self, index, tile):
                indexer.scores = lambda self, q, weight, k: jnp.broadcast_to(
                    jnp.arange(k.shape[1], dtype=jnp.float32),
                    (q.shape[0], q.shape[2], k.shape[1]))
                try:
                    return choose(self, index, tile)
                finally:
                    indexer.scores = scores
            indexer.choose = last_keys
        elif name == "k_halved":
            cfg = _with_kwargs(cfg, topk=_factory_topk(cfg) // 2)
        elif name == "relu_dropped":
            indexer.scores = scored(lambda z: z, lambda w: w)
        elif name == "w_dropped":
            indexer.scores = scored(jax.nn.relu, jnp.ones_like)
        elif name == "kl_dropped":
            cfg = _with_kwargs(cfg, index_weight=0.0)
        elif name == "u_attached":
            sdar_moe.lax = attached
        elif name == "p_attached":
            keye_vl.lax = attached

            def differentiated(ix, scale, index, q, k, lse, bias):
                fn = lambda total, *block: (total + jax.checkpoint(  # noqa: E731
                    lambda *b: ix.kl_of_block(scale, *b))(*block), ())
                total, _ = keye_vl._kl_blocks(ix, scale, index, q, k, lse, bias,
                                              fn, jnp.zeros((), jnp.float32))
                return total / (q.shape[0] * q.shape[2])
            keye_vl._index_kl = differentiated
        elif name == "not_renormalised":
            pallas_attention.selected_attention = unrenormalised(saved[7], 0)
            sdar_moe.GQA._chosen = unrenormalised(saved[6], 1)
        elif name == "mrope_collapsed":
            rows = saved[8]
            pallas_rope._rows = lambda spans, s: np.broadcast_to(
                rows(spans, s)[:1], (3, s))
            retrace_rope()
        elif name == "index_rope_dropped":
            keye_vl.rope = lambda x, theta, positions=None: x
        else:
            rounded.ROUND = jnp.dtype(name)
            reference._programs.cache_clear()
        yield common.build_model(cfg)
    finally:
        (indexer.scores, indexer.choose, keye_vl.rope, keye_vl.lax,
         keye_vl._index_kl, sdar_moe.lax, sdar_moe.GQA._chosen,
         pallas_attention.selected_attention, pallas_rope._rows) = saved
        if name == "mrope_collapsed":
            retrace_rope()
        if rounded.ROUND is not None:
            rounded.ROUND = None
            reference._programs.cache_clear()


def init_comparer(cfg, traffic, model, reference):
    """seed -> one row: the cell's own check
    (`train_zoo_tokens_gradnorm.checker`) and what it compared."""
    check = train_zoo_tokens_gradnorm.checker(cfg, traffic, model, reference)

    def compare(seed):
        notes = {}
        correct = check(seed, notes)
        losses, held = notes["check_losses"], notes["check_rows_held"]
        return {
            "seed": seed, "correct": correct, **notes,
            "loss_gaps": [abs(a / b - 1) for a, b in zip(
                losses["system"], losses["reference"])],
            "rows_gap": max(abs(a - b) for got, want in zip(
                held["system"], held["reference"]) for a, b in zip(got, want)),
            "step_moves_loss": abs(
                losses["reference"][1] / losses["reference"][0] - 1)}

    return compare


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
