"""ctypes bindings for the native C++ data runtime (native/*.cc).

Two components:

- **idx loader** (native/mnist_loader.cc ≙ Sequential/mnist.h:79-160):
  same magic/big-endian/28×28/error-code contract as the pure-NumPy parser
  in data/mnist.py, raised as the same typed `MnistError`s. The Python side
  owns every allocation — the C side fills caller-provided NumPy buffers,
  so no ownership crosses the FFI boundary.

- **prefetching batcher** (native/batcher.cc): a worker thread assembles
  shuffled batches into a ring of slots while the device trains; `Batcher`
  wraps acquire/release into an iterator yielding zero-copy NumPy views.

The shared library is built lazily with `make` on first import; import
fails cleanly (ImportError) when no toolchain is available and
data/pipeline.py falls back to the NumPy parser.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Iterator, Tuple

import numpy as np

from parallel_cnn_tpu.data.mnist import MnistError

# Chaos/ops escape hatch: force the no-native fallback path without
# touching the filesystem (resilience/chaos.py hidden_native_lib uses it
# to prove pipeline.py's NumPy degradation deterministically).
if os.environ.get("PCNN_DISABLE_NATIVE") == "1":  # graftcheck: disable=env-outside-config -- chaos escape hatch evaluated at import, before any Config object exists
    raise ImportError("native runtime disabled via PCNN_DISABLE_NATIVE=1")

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libpcnn_native.so")


_STAMP_PATH = _LIB_PATH + ".stamp"
_SOURCES = ("mnist_loader.cc", "batcher.cc", "Makefile")


def _source_digest() -> str:
    """sha256 over the tracked native sources (and the Makefile whose
    flags shape the binary) — what a trusted .so must have been built
    from."""
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read() + b"\0")
    return h.hexdigest()


def _build() -> None:
    """Build the shared library unless THIS code already built it from
    the sources on disk.

    Staleness is decided by content, not mtime: a checkout or a copied
    tree does not preserve mtimes meaningfully, and a .so that arrived
    from elsewhere must not be trusted. The build records the sources'
    digest in a stamp file next to the .so; a missing library, a missing
    stamp, or a stamp that does not match the sources all rebuild."""
    digest = _source_digest()
    try:
        with open(_STAMP_PATH) as f:
            fresh = os.path.exists(_LIB_PATH) and f.read().strip() == digest
    except OSError:
        fresh = False
    if fresh:
        return
    try:
        proc = subprocess.run(
            ["make", "-B", "-C", _NATIVE_DIR],
            capture_output=True,
            text=True,
        )
    except OSError as e:  # no `make` at all — degrade like a build failure
        raise ImportError(f"native build unavailable: {e}") from e
    if proc.returncode != 0:
        raise ImportError(
            f"native build failed:\n{proc.stdout}\n{proc.stderr}"
        )
    tmp = f"{_STAMP_PATH}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(digest + "\n")
    os.replace(tmp, _STAMP_PATH)


def _load_lib() -> ctypes.CDLL:
    _build()
    lib = ctypes.CDLL(_LIB_PATH)
    lib.pcnn_mnist_image_count.restype = ctypes.c_long
    lib.pcnn_mnist_image_count.argtypes = [ctypes.c_char_p]
    lib.pcnn_mnist_load_images.restype = ctypes.c_long
    lib.pcnn_mnist_load_images.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_long,
    ]
    lib.pcnn_mnist_label_count.restype = ctypes.c_long
    lib.pcnn_mnist_label_count.argtypes = [ctypes.c_char_p]
    lib.pcnn_mnist_load_labels.restype = ctypes.c_long
    lib.pcnn_mnist_load_labels.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_long,
    ]
    lib.pcnn_batcher_create.restype = ctypes.c_void_p
    lib.pcnn_batcher_create.argtypes = [
        ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_long,  # n
        ctypes.c_long,  # sample_size
        ctypes.c_long,  # batch
        ctypes.c_long,  # depth
        ctypes.c_uint64,
        ctypes.c_int,
    ]
    lib.pcnn_batcher_acquire.restype = ctypes.c_long
    lib.pcnn_batcher_acquire.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
    ]
    lib.pcnn_batcher_release.restype = None
    lib.pcnn_batcher_release.argtypes = [ctypes.c_void_p]
    lib.pcnn_batcher_destroy.restype = None
    lib.pcnn_batcher_destroy.argtypes = [ctypes.c_void_p]
    return lib


def _load_lib_with_retry() -> ctypes.CDLL:
    """dlopen can fail transiently on shared filesystems (a sibling process
    mid-`os.replace` of the .so, NFS attribute-cache lag): retry briefly
    before degrading to the NumPy fallback. ImportError (no toolchain) is
    permanent and not retried."""
    from parallel_cnn_tpu.resilience.retry import RetryPolicy, retry_call

    policy = RetryPolicy(
        attempts=int(os.environ.get("PCNN_NATIVE_RETRIES", "2")),  # graftcheck: disable=env-outside-config -- loader-internal retry knob read at call time; no Config flows through the native boundary
        base_delay=0.1,
        max_delay=1.0,
    )
    return retry_call(
        _load_lib, policy=policy, retry_on=(OSError,), describe="native dlopen"
    )


_lib = _load_lib_with_retry()

_ERROR_MESSAGES = {
    -1: "no such file",
    -2: "not a valid image file",
    -3: "not a valid label file",
    -4: "element counts mismatch",
}


def _check(code: int, path: str) -> None:
    if code < 0:
        raise MnistError(code, f"{_ERROR_MESSAGES.get(code, 'error')}: {path}")


def load_idx_images(path: str) -> np.ndarray:
    """(N, 28, 28) float32 in [0,1] via the native parser."""
    cpath = os.fsencode(path)
    n = _lib.pcnn_mnist_image_count(cpath)
    _check(n, path)
    out = np.empty((n, 28, 28), dtype=np.float32)
    rc = _lib.pcnn_mnist_load_images(
        cpath, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n
    )
    _check(rc, path)
    return out


def load_idx_labels(path: str) -> np.ndarray:
    """(N,) int32 via the native parser."""
    cpath = os.fsencode(path)
    n = _lib.pcnn_mnist_label_count(cpath)
    _check(n, path)
    out = np.empty((n,), dtype=np.int32)
    rc = _lib.pcnn_mnist_load_labels(
        cpath, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), n
    )
    _check(rc, path)
    return out


def load_pair(image_path: str, label_path: str) -> Tuple[np.ndarray, np.ndarray]:
    """≙ mnist_load(image_file, label_file, …) with the count-mismatch check
    (Sequential/mnist.h:118-121)."""
    images = load_idx_images(image_path)
    labels = load_idx_labels(label_path)
    if images.shape[0] != labels.shape[0]:
        raise MnistError(
            -4,
            f"element counts mismatch: {images.shape[0]} images vs "
            f"{labels.shape[0]} labels",
        )
    return images, labels


class Batcher:
    """Iterator over prefetched (images, labels) batches.

    Wraps the native ring-buffer pipeline: batches are assembled on a C++
    worker thread concurrently with consumer work. Runs forever (epochs
    wrap, reshuffling when shuffle=True); bound iteration with
    `itertools.islice` or `steps_per_epoch`.

    Shape-generic: images may be (N, 28, 28) MNIST, (N, 32, 32, 3) CIFAR,
    or any (N, ...) float32 array — the ring copies flat samples and the
    views are reshaped back to the per-sample shape.

    copy=True (default) hands out freshly-owned arrays, safe to pass to
    asynchronous consumers (jax.device_put's H2D may still be in flight
    when the next batch is requested). copy=False hands out zero-copy views
    into the ring slot, valid only until the next iteration — for consumers
    that synchronously drain the buffer.
    """

    def __init__(
        self,
        images: np.ndarray,
        labels: np.ndarray,
        batch_size: int,
        *,
        depth: int = 4,
        seed: int = 0,
        shuffle: bool = True,
        copy: bool = True,
    ):
        self._images = np.ascontiguousarray(images, dtype=np.float32)
        self._labels = np.ascontiguousarray(labels, dtype=np.int32)
        if self._images.shape[0] != self._labels.shape[0]:
            raise ValueError("images/labels count mismatch")
        if batch_size > self._images.shape[0]:
            # The ring fill would wrap mid-batch and silently duplicate
            # samples within a single batch (and reshuffle mid-batch).
            raise ValueError(
                f"batch_size {batch_size} exceeds dataset size "
                f"{self._images.shape[0]}"
            )
        self.batch_size = batch_size
        self._sample_shape = self._images.shape[1:]
        sample_size = 1
        for d in self._sample_shape:
            sample_size *= d
        self._handle = _lib.pcnn_batcher_create(
            self._images.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self._labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            self._images.shape[0],
            sample_size,
            batch_size,
            depth,
            seed,
            int(shuffle),
        )
        if not self._handle:
            raise RuntimeError("pcnn_batcher_create failed")
        self._copy = copy
        self._pending_release = False

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        return self

    def __next__(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._handle is None:
            raise StopIteration
        # Deferred release: the previous batch's views stay valid until the
        # consumer asks for the next one (the producer may then refill).
        if self._pending_release:
            _lib.pcnn_batcher_release(self._handle)
            self._pending_release = False
        xp = ctypes.POINTER(ctypes.c_float)()
        yp = ctypes.POINTER(ctypes.c_int32)()
        rc = _lib.pcnn_batcher_acquire(
            self._handle, ctypes.byref(xp), ctypes.byref(yp)
        )
        if rc != 0:
            raise StopIteration
        x = np.ctypeslib.as_array(xp, shape=(self.batch_size,) + self._sample_shape)
        y = np.ctypeslib.as_array(yp, shape=(self.batch_size,))
        if self._copy:
            x, y = x.copy(), y.copy()
            _lib.pcnn_batcher_release(self._handle)
        else:
            self._pending_release = True
        return x, y

    def close(self) -> None:
        # getattr: __del__ runs even when __init__ raised before _handle
        # was assigned (e.g. the batch_size > n rejection).
        if getattr(self, "_handle", None) is not None:
            _lib.pcnn_batcher_destroy(self._handle)
            self._handle = None

    def __enter__(self) -> "Batcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self) -> None:
        self.close()
