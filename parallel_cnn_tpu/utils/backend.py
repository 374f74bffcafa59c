"""Backend identification, chip peaks, and the persistent compile cache.

Three things every entry point (cli.main, chip_smoke.py,
benchmark/run.py) needs to agree on:

- what counts as a TPU (`is_tpu`: the first device's platform is "tpu" —
  the single switch between compiled Mosaic and the Pallas interpreter,
  ops/pallas.py:_interpret);
- the published peak of the chip a number was measured on (`peak_flops`:
  a lookup by ``device_kind`` that refuses kinds it does not know — an
  MFU against an assumed peak is not a measurement);
- where XLA's persistent compilation cache lives (`enable_compile_cache`).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax

# Published bf16 matmul peak per chip, FLOP/s, keyed by the string JAX
# reports as ``device_kind``. Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16 per chip). One table; a kind that is not here is an
# error, never a default.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


class UnknownDeviceKind(ValueError):
    """`peak_flops` was asked about a chip it has no published peak for."""


def is_tpu(devices: Optional[Sequence] = None) -> bool:
    """True iff the (default) backend's first device is a TPU."""
    ds = list(devices) if devices is not None else jax.devices()
    return bool(ds) and ds[0].platform == "tpu"


def peak_flops(device_kind: str) -> float:
    """Published bf16 peak FLOP/s of one chip of this kind."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"no published peak for device_kind {device_kind!r} "
            f"(known: {sorted(PEAK_BF16_FLOPS)}); add it to "
            "utils/backend.py:PEAK_BF16_FLOPS with its source"
        ) from None


def enable_compile_cache() -> str:
    """Point XLA's persistent compilation cache somewhere stable and
    return the directory in use.

    Call first thing in an entry point, never at import. When
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already honours it, so this
    sets nothing (the operator placed the cache; the path is part of the
    cache key, so it must not be overridden). Otherwise the cache is the
    fixed ``<checkout>/.jax_cache``. The serve tier's ``--aot-cache-dir``
    (serialized executables) is a different, opt-in thing.

    Also starts the process's compile log (``obs/compiles.py``): what
    the cache saves, and what it does not, is recorded from here on.
    """
    from parallel_cnn_tpu.obs import compiles

    compiles.install()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")  # graftcheck: disable=env-outside-config -- JAX's own deployment variable, read only to decide NOT to override it
    if placed:
        return placed
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
