"""Synthetic token sequences, made from the seed on the device in one
jitted call: ids uniform over a vocabulary (or the slice of one that a
chip holds), and for every position the token that follows it."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "length", "vocab"))
def synthetic_tokens(key, *, n, length, vocab):
    """(tokens (n, length), targets (n, length)) int32: `targets[:, i]` is
    the token after `tokens[:, i]`, so the last target is a token no
    sequence holds (`length + 1` ids are drawn a sequence). No padding,
    no packing."""
    ids = jax.random.randint(key, (n, length + 1), 0, vocab, jnp.int32)
    return ids[:, :-1], ids[:, 1:]
