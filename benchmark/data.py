"""Synthetic inputs, made from the seed. Training sets are made on the
device in one jitted call (4,096 images at 224x224 in NumPy would be tens
of seconds of set-up in every run); request payloads are made on the host,
where requests come from."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(
    jax.jit, static_argnames=("n", "hw", "channels", "classes", "dtype", "chunk"))
def synthetic_images(key, *, n, hw, channels, classes, dtype, chunk,
                     noise=0.1):
    """A learnable image classification set, as data/synthetic.py:
    make_image_dataset builds it: one smooth prototype per class (low-res
    uniform noise upsampled 4x) plus Gaussian noise, clipped to [0, 1].
    Built `chunk` images at a time so the float32 temporaries stay far
    below what training itself needs. Returns (images NHWC, labels)."""
    h, w = hw
    if n % chunk:
        raise ValueError(f"{n} images are not a multiple of the chunk {chunk}")
    kp, kl, kn = jax.random.split(key, 3)
    low = jax.random.uniform(kp, (classes, -(-h // 4), -(-w // 4), channels))
    labels = jax.random.randint(kl, (n,), 0, classes)

    def one_chunk(args):
        lab, k = args
        img = jnp.repeat(jnp.repeat(low[lab], 4, axis=1), 4, axis=2)[:, :h, :w]
        img = img + noise * jax.random.normal(k, img.shape)
        return jnp.clip(img, 0.0, 1.0).astype(dtype)

    keys = jax.random.split(kn, n // chunk)
    images = jax.lax.map(one_chunk, (labels.reshape(-1, chunk), keys))
    return images.reshape(n, h, w, channels), labels


def request_payloads(seed: int, n: int, in_shape) -> np.ndarray:
    """n distinct float32 samples in [0, 1), on the host."""
    rng = np.random.default_rng([int(seed), 0x9A71])
    return rng.random((n, *in_shape), dtype=np.float32)
