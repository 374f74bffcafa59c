"""What the token families' suites share (tests/test_glm_moe.py,
test_sdar_moe.py, test_afmoe.py, test_bailing_hybrid.py; not collected):
the toy a file builds once, and its evaluations written the cheap way once.
Every evaluation of a model or a layer is one compiled program at the
highest matmul precision, and the toy's own (`system`, `loss`, `logits`,
`steps`) are made once a file: the second test to ask gets the first one's arrays and no
trace. Op by op the same toy costs ten times its compile at every call
(README.md, "Writing tier-1 tests").

A planted fault is a patch of a module's function, which neither a memo nor
a `jax.jit` object that was traced before can see: inside one, and wherever
a NEW trace is the point, ask with `fresh=True`.

What the reference side needs is not here: benchmark/reference/* jits its
programs and keeps them by architecture."""

import functools
import os
import sys
import types

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tools.compare_glm_moe import random_leaves  # noqa: E402
from parallel_cnn_tpu.train import zoo  # noqa: E402

HYPER = dict(lr=1e-3, kind="adamw", b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)


def highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def jitted(fn, *args):
    """`fn(*args)` as one compiled program. Hand the arrays over as
    arguments: what a closure holds is a constant of the program."""
    return highest(jax.jit(fn), *args)


def pulled(fn, cotangent, *args):
    """`fn(*args)` and its arguments' cotangents for `cotangent`, forward
    and backward in one compiled program."""
    def run(cotangent, *args):
        out, pull = jax.vjp(fn, *args)
        return out, pull(cotangent)

    return jitted(run, cotangent, *args)


def toy(model, arch, *, seq, batch=4, shifted=False, draw_state=True,
        adjust=None, compiled=False):
    """A file's `small`: `model` initialised for `seq` positions, every
    parameter leaf redrawn (`random_leaves`: weights of std 1 / sqrt(fan_in),
    gains 1 + 0.1 n) and, with `draw_state`, every selection bias 0.01 n;
    `adjust(params)` has the last word. `batch` sequences of random tokens,
    the targets the tokens rolled by one (`shifted`: one token more drawn,
    inputs and targets its two ends). The keys are the files' own since
    their first PR: 1 to initialise, 2 to redraw, 3 for the tokens.
    `compiled`: initialised and redrawn in one program, not leaf by leaf (a
    model of forty leaves: 1 s, not 8)."""
    def draw(first, second):
        params, state, _ = model.init(first, (seq,))
        return (state, *random_leaves(params, state, second))

    state, params, drawn = (jax.jit(draw) if compiled else draw)(
        jax.random.key(1), jax.random.key(2))
    if draw_state:
        state = drawn
    if adjust is not None:
        params = adjust(params)
    tokens = jax.random.randint(jax.random.key(3), (batch, seq + shifted), 0,
                                arch["vocab_size"])
    x, y = ((tokens[:, :-1], tokens[:, 1:]) if shifted
            else (tokens, jnp.roll(tokens, -1, axis=1)))
    return types.SimpleNamespace(model=model, arch=arch, params=params,
                                 state=state, x=x, y=y, memo={})


@functools.lru_cache(maxsize=None)
def _program(kind, model, accum=1):
    """One `jax.jit` object a model and a kind: the models are frozen
    dataclasses, and another `dtype` is another key."""
    if kind == "step":
        return zoo.make_train_step(model, zoo.make_optimizer(**HYPER), accum, None)
    fn = model.apply if kind == "apply" else zoo._build_loss_fn(model, None)
    if kind == "system":
        fn = jax.value_and_grad(fn, has_aux=True)
    # a function of its own: jit keeps its traces by the function, and a
    # model's bound `loss` is the same function every time it is asked for,
    # so a `fresh` program of it would be served the trace of the kept one
    return jax.jit(lambda *args: fn(*args))


def _made_once(s, key, make, fresh=False, keep=True):
    """`make(program)`, kept with the toy under `key`; `fresh`: from
    programs nobody has traced, and not kept."""
    if fresh:
        return make(_program.__wrapped__)
    if not keep:
        return make(_program)
    if key not in s.memo:
        s.memo[key] = make(_program)
    return s.memo[key]


def system(s, model=None, fresh=False):
    """(loss, every leaf's gradient, the new state) of the model's own loss
    on the toy. Do not change what comes back: the next test is handed the
    same arrays. `fresh`: a new trace, nothing read from or kept in the memo."""
    model = model or s.model

    def make(program):
        (value, new), grads = highest(program("system", model), s.params,
                                      s.state, s.x, s.y)
        return float(value), grads, new

    return _made_once(s, ("system", model), make, fresh)


def loss(s, model=None, fresh=False, **other):
    """The loss alone, for the tests that read nothing else; `other` puts
    another `params`, `state`, `x` or `y` in the toy's place (through the
    same program: equal to the bit means what it meant)."""
    model = model or s.model

    def make(program):
        at = {"params": s.params, "state": s.state, "x": s.x, "y": s.y, **other}
        return float(highest(program("loss", model), at["params"], at["state"],
                             at["x"], at["y"])[0])

    return _made_once(s, ("loss", model), make, fresh, keep=not other)


def logits(s, model=None):
    """`model.apply` on the toy's tokens (no step: the state is not kept)."""
    model = model or s.model

    def make(program):
        return highest(program("apply", model), s.params, s.state, s.x)[0]

    return _made_once(s, ("logits", model), make)


def stepped(s, model=None, accum=1):
    """A `ZooState` of copies of the toy's parameters and state (the step
    donates what it is handed), and `zoo.make_train_step` (the GSPMD step, no
    mesh) under the files' AdamW: one program a model and an accumulation."""
    copied = lambda tree: jax.tree_util.tree_map(lambda a: a + 0, tree)  # noqa: E731
    opt = zoo.make_optimizer(**HYPER)
    return (zoo.ZooState(copied(s.params), copied(s.state), opt.init(s.params)),
            _program("step", model or s.model, accum))


def steps(s, n=3, model=None):
    """`n` steps on the toy's one batch: the losses, the model's counters
    after each step, and the last state."""
    model = model or s.model
    key = ("steps", model, n)
    if key not in s.memo:
        state, step = stepped(s, model)
        losses, seen = [], []
        for _ in range(n):
            state, value = highest(step, state, s.x, s.y)
            losses.append(float(value))
            seen.append(model.counters(state.model_state))
        s.memo[key] = losses, seen, state
    return s.memo[key]
