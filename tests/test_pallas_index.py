"""ops/pallas_index.py — the indexer's scores as one kernel a direction —
on the CPU: both kernels in interpret mode against `Indexer.scores`' plain
form and `jax.vjp` of it, a block of queries against a band of keys; weights
of either sign and zero; a product that is exactly 0; the key tiles past the
block, skipped, after the callers' masks; what `tile` refuses, and that
`Indexer.block_scores` then runs the plain form — as it does while the class
holds another equation; the kernels through an attention layer's two loss
terms. That Mosaic takes them at the cell's shapes, and what surrounds them
in a compiled layer, is tests/test_compiled_keye_programs.py's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_cnn_tpu.nn import keye_vl
from parallel_cnn_tpu.ops import pallas_index as pi
from token_family import jitted

H, D, ROWS = 3, 64, 128
IX = keye_vl.Indexer(heads=H, head_dim=D, topk=8, rows=ROWS)
C = (H * D) ** -0.5
# name -> (N, keys, the block's first position): one key tile of 1,024 with
# nothing skipped; three of 512 with the block in the first (two skipped),
# across the first two (one skipped: the band ends ahead of the block's own
# quarter) and in the last (none)
CASES = {"one_tile": (2, 1024, 896), "first_of_three": (1, 1536, 0),
         "across_two": (2, 1536, 448), "last_of_three": (1, 1536, 1408)}
GRADS = ("dq", "dw", "dk")


def _f32(a):
    return np.asarray(a.astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _block(case: str, dtype: str):
    """A case's operands, the plain form's scores and gradients under the
    callers' mask, and the kernels' (interpret mode)."""
    n, keys, at = CASES[case]
    ks = jax.random.split(jax.random.key(len(case)), 4)
    q = jax.random.normal(ks[0], (n, H, ROWS, D)).astype(dtype)
    # of either sign, and a head that weighs nothing for some queries
    w = jax.random.normal(ks[1], (n, ROWS, H)).at[:, ::5, 1].set(0.0).astype(dtype)
    k = jax.random.normal(ks[2], (n, keys, D)).astype(dtype)
    # the callers' rule: no key after the query
    seen = (jnp.arange(keys)[None, :] <= at + jnp.arange(ROWS)[:, None])[None]
    d_i = jnp.where(seen, jax.random.normal(ks[3], (n, ROWS, keys)), 0.0)
    t = pi.tile(ROWS, keys, D)
    want, pull = jax.vjp(IX.scores, q, w, k)
    at = jnp.int32(at)
    return dict(
        operands=(q, w, k), at=at, t=t, seen=seen, d_i=d_i, want=want,
        want_grads=dict(zip(GRADS, pull(d_i))),
        got=pi.forward(q, w, k, at, c=C, t=t, interpret=True),
        got_grads=dict(zip(GRADS, pi.backward(q, w, k, at, d_i, c=C, t=t,
                                              interpret=True))))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_forward_is_the_plain_form_to_float32s_summation_order(case, dtype):
    """Products from the operands' dtype, everything after them float32 on
    both sides: what differs is the order the heads are summed in."""
    b = _block(case, dtype)
    assert b["got"].dtype == jnp.float32 and b["got"].shape == b["want"].shape
    scale = float(jnp.max(jnp.abs(b["want"])))
    np.testing.assert_allclose(jnp.where(b["seen"], b["got"], 0.0),
                               jnp.where(b["seen"], b["want"], 0.0),
                               atol=2e-6 * scale)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("grad", GRADS)
@pytest.mark.parametrize("case", CASES)
def test_backward_is_autodiff_of_the_plain_form(case, grad, dtype):
    """In the operands' dtypes. float32: to float32's floor. bf16: the
    kernel rounds `g` to bf16 as an MXU operand — what the chip's plain
    product does with its float32 side, and this host's does not — so to
    that rounding (seen: 0.4-0.6 % of the largest entry)."""
    b = _block(case, dtype)
    got, want = b["got_grads"][grad], b["want_grads"][grad]
    assert got.dtype == want.dtype == jnp.dtype(dtype) and got.shape == want.shape
    tol = 2.0 ** -6 if dtype == "bfloat16" else 5e-6
    np.testing.assert_allclose(_f32(got), _f32(want),
                               atol=tol * np.abs(_f32(want)).max())


@pytest.mark.parametrize("case", ["first_of_three", "across_two"])
def test_key_tiles_past_the_block_are_skipped_and_read_zero(case):
    """A tile wholly past the block's last query is not computed: zeros in
    the scores (the callers mask them: what the plain form holds there is
    no zero) and in `dk`, whatever the cotangent says there."""
    b = _block(case, "float32")
    first_skipped = ((int(b["at"]) + ROWS - 1) // b["t"] + 1) * b["t"]
    assert first_skipped < b["got"].shape[-1]
    assert not np.asarray(b["got"][..., first_skipped:]).any()
    assert np.asarray(b["got"][..., :first_skipped]).any()
    assert np.asarray(b["want"][..., first_skipped:]).any()
    everywhere = jnp.ones_like(b["d_i"])
    _, _, dk = pi.backward(*b["operands"], b["at"], everywhere, c=C, t=b["t"],
                           interpret=True)
    assert not np.asarray(dk[:, first_skipped:]).any()
    assert np.asarray(dk[:, :first_skipped]).any()


@pytest.mark.parametrize("grad", GRADS)
def test_a_product_that_is_exactly_zero_passes_no_gradient(grad):
    """`jax.nn.relu`'s gradient at 0 is 0: a query head of zeros (its `z`
    is 0 against every key) gets no `dq`, adds nothing to `dk`, and its
    weight's gradient is 0 — as autodiff of the plain form says."""
    b = _block("one_tile", "float32")
    q, w, k = b["operands"]
    q = q.at[:, 1, 3].set(0.0)
    k = k.at[:, 17].set(0.0)  # and a key every head's product with is 0
    want = dict(zip(GRADS, jax.vjp(IX.scores, q, w, k)[1](b["d_i"])))
    got = dict(zip(GRADS, pi.backward(q, w, k, b["at"], b["d_i"], c=C, t=b["t"],
                                      interpret=True)))
    np.testing.assert_allclose(got[grad], want[grad],
                               atol=5e-6 * np.abs(np.asarray(want[grad])).max())
    zero = {"dq": got["dq"][:, 1, 3], "dw": got["dw"][:, 3, 1],
            "dk": got["dk"][:, 17]}[grad]
    assert not np.asarray(zero).any()


@pytest.mark.parametrize("rows,keys,d,tile", [
    (256, 16384, 64, 1024), (256, 12288, 64, 1024), (256, 4096, 64, 1024),
    (128, 1536, 64, 512), (256, 512, 128, 512), (256, 768, 64, 256),
    (256, 640, 64, None),    # keys: no whole tile
    (64, 1024, 64, None),    # queries: less than a register's lanes
    (256, 1024, 8, None),    # the toys' head width
])
def test_tile_takes_whole_registers_of_queries_and_keys(rows, keys, d, tile):
    assert pi.tile(rows, keys, d) == tile


def _spy(monkeypatch):
    """`pi.either` as the kernels in interpret mode (the platform would send
    this host to `otherwise`), and the (keys, back) it ran at, in order."""
    ran = []

    def either(*operands, c, t, back, otherwise):
        ran.append((operands[2].shape[1], back))
        kernel = pi.backward if back else pi.forward
        return kernel(*operands, c=c, t=t, interpret=True)

    monkeypatch.setattr(pi, "either", either)
    return ran


def test_shapes_that_do_not_tile_run_the_plain_form_to_the_bit(monkeypatch):
    ran = _spy(monkeypatch)
    q, w, k = _block("one_tile", "float32")["operands"]
    k = k[:, :640]
    got = jitted(lambda *a: IX.block_scores(*a, jnp.int32(0)), q, w, k)
    np.testing.assert_array_equal(got, jitted(IX.scores, q, w, k))
    assert not ran


def test_a_program_lowered_for_this_host_runs_the_plain_branch():
    """Shapes that tile, through the `custom_vjp` and `either`'s choice:
    the plain form's values, and autodiff's gradients, to the bit."""
    b = _block("across_two", "float32")

    def both(fn):
        return jitted(lambda q, w, k, d_i: jax.vjp(fn, q, w, k)[1](d_i)
                      + (fn(q, w, k),), *b["operands"], b["d_i"])

    got = both(lambda *a: IX.block_scores(*a, b["at"]))
    for a, want in zip(got, both(IX.scores)):
        np.testing.assert_array_equal(a, want)
    text = jax.jit(lambda *a: IX.block_scores(*a, b["at"])).lower(
        *b["operands"]).as_text()
    assert "index_scores" not in text  # no kernel in a CPU's program


def test_another_equation_under_the_name_runs_as_it_is_written(monkeypatch):
    """A comparison tool plants its faults by REPLACING `Indexer.scores`
    (benchmark/tools/compare_keye_vl.py: `relu_dropped`, `w_dropped`,
    `window`): while the class holds another function the kernels, which
    implement the equation this module wrote, stand aside."""
    ran = _spy(monkeypatch)
    b = _block("one_tile", "float32")
    clean = IX.block_scores(*b["operands"], b["at"])
    assert ran == [(1024, False)]
    monkeypatch.setattr(keye_vl.Indexer, "scores",
                        lambda self, q, weight, k: 2.0 * keye_vl._SCORES(
                            self, q, weight, k))
    doubled = IX.block_scores(*b["operands"], b["at"])
    assert ran == [(1024, False)]
    np.testing.assert_allclose(jnp.where(b["seen"], doubled, 0.0),
                               2.0 * jnp.where(b["seen"], clean, 0.0), atol=1e-5)


def test_an_attention_layers_two_terms_through_the_kernels(monkeypatch):
    """One attention layer of 512 positions in four bands of a 128-query
    block (two of the bands tile), value and
    the gradient of both loss terms: with the kernels where the shapes tile
    as without them. The selection reads the kernel's scores (`choose`),
    the objective reads them again (`kl_of_block`) and its backward meets
    the backward kernel inside `_index_kl_bwd`'s `jax.grad` of a block."""
    s, hidden = 512, 32
    att = keye_vl.keye_vl(
        vocab_size=64, hidden_size=hidden, moe_intermediate_size=16,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=1,
        head_dim=16, num_experts=4, num_experts_per_tok=2,
        indexer_num_heads=2, indexer_head_dim=64, topk=64,
        mrope_section=[2, 2, 4], dtype="float32", q_block=128,
        index_block=128, loss_block=128).attn
    params = jitted(lambda key: jax.tree_util.tree_map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.key(a.size), a.shape),
        att.init(key, (s, hidden))[0]), jax.random.key(2))
    x = jax.random.normal(jax.random.key(3), (1, s, hidden))

    def both_terms(p, x):
        out, report = att.apply(p, {}, x, True)
        return jnp.sum(out * out) + report["kl"], report["keys_selected_mean"]

    # (a function of its own each time: jit keeps its traces by function,
    # and `either` is a global the trace reads)
    plain = jitted(jax.value_and_grad(lambda p, x: both_terms(p, x), has_aux=True),
                   params, x)
    ran = _spy(monkeypatch)
    fused = jitted(jax.value_and_grad(lambda p, x: both_terms(p, x), has_aux=True),
                   params, x)
    # the selection, the objective, and the objective again in its backward
    # with the backward kernel behind it, a band at a time: the second
    # band's 256 keys and the last one's 512 tile, 128 and 384 do not
    bands = [(256, False), (512, False)]
    assert ran == bands + bands + [(256, False), (256, True), (512, False),
                                   (512, True)]
    assert float(fused[0][1]) == float(plain[0][1])  # the same keys were chosen
    np.testing.assert_allclose(fused[0][0], plain[0][0], rtol=1e-5)
    for got, want in zip(jax.tree_util.tree_leaves(fused[1]),
                         jax.tree_util.tree_leaves(plain[1])):
        np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    assert float(jnp.max(jnp.abs(fused[1]["indexer"]["q"]))) > 0
