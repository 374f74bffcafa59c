"""Module protocol + Sequential combinator.

A Module is a value (dataclass) with two pure functions:

    init(key, in_shape) -> (params, state, out_shape)
    apply(params, state, x, train) -> (y, new_state)

`in_shape`/`out_shape` are per-sample shapes (no batch dim); `x` is always
batched (N, ...). params hold trainables; state holds non-trainables
(BatchNorm running stats). Layers without params/state use empty dicts so
pytree structures stay uniform and checkpoint/optimizer code needs no
special cases.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import jax

Params = Any
State = Any
Shape = Tuple[int, ...]


class Module:
    """Base class (interface only — subclasses are frozen dataclasses)."""

    def init(self, key: jax.Array, in_shape: Shape):
        raise NotImplementedError

    def apply(self, params, state, x, train: bool = False):
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class Sequential(Module):
    """Compose modules; params/state are lists aligned with `layers`.

    `apply` opens a `jax.named_scope` per child, so every HLO instruction
    of a compiled program carries the layer it came from in its
    `op_name` metadata (read back by `obs/programs.py`). `names` gives the
    scopes (one per layer); without it a child is `<index>.<ClassName>`.
    Scopes are metadata only: they are not part of `init`'s output, so
    parameter/state pytrees, checkpoints and plan fingerprints do not
    depend on them, and neither does the compile-cache key."""

    layers: Sequence[Module]
    names: Optional[Sequence[str]] = None

    def scope_names(self) -> List[str]:
        if self.names is not None:
            if len(self.names) != len(self.layers):
                raise ValueError(
                    f"{len(self.names)} names for {len(self.layers)} layers"
                )
            return list(self.names)
        return [f"{i}.{type(l).__name__}" for i, l in enumerate(self.layers)]

    def init(self, key: jax.Array, in_shape: Shape):
        params: List[Params] = []
        state: List[State] = []
        shape = in_shape
        keys = jax.random.split(key, max(len(self.layers), 1))
        for layer, k in zip(self.layers, keys):
            p, s, shape = layer.init(k, shape)
            params.append(p)
            state.append(s)
        return params, state, shape

    def apply(self, params, state, x, train: bool = False):
        new_state: List[State] = []
        for layer, name, p, s in zip(
            self.layers, self.scope_names(), params, state, strict=True
        ):
            with jax.named_scope(name):
                x, s = layer.apply(p, s, x, train)
            new_state.append(s)
        return x, new_state
