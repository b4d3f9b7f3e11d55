"""Native runtime kernels — compile-on-first-use C++ with ctypes bindings.

(reference keeps its performance-critical edge/runtime code in C++:
android/fedmlsdk/MobileNN/ — on-device trainer + C++ LightSecAgg. Here the
native tier provides the TPU-framework analogs: finite-field SecAgg kernels,
a jax-free edge trainer, and a wire-integrity checksum; see
fedml_native.cpp's header for the inventory.)

The .so builds lazily with g++ (baked into the image; pybind11 is not, so
bindings are plain ctypes over an extern-C ABI) and is NAMED by a hash of
fedml_native.cpp: a binary built from other source — a stale one, or a
stray one that rode in with a copy of the tree — has another name and is
never loaded (file times say nothing once a tree has been copied). Every
caller has a numpy fallback: `available()` is False and everything still
works when no compiler is present.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "fedml_native.cpp")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"libfedml_native.{digest}.so")


def _build(so: str) -> bool:
    # compile to a per-pid temp path, then atomically rename: concurrent
    # processes racing on the shared .so would otherwise dlopen a
    # half-written file (or SIGBUS on truncated mapped pages)
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        log.info("native build unavailable (%s); using numpy fallbacks", e)
        return False
    if r.returncode != 0:
        log.warning("native build failed; using numpy fallbacks:\n%s",
                    r.stderr[-2000:])
        return False
    os.replace(tmp, so)
    # binaries of other source versions are dead weight from here on
    for old in glob.glob(os.path.join(_HERE, "libfedml_native*.so")):
        if old != so:
            try:
                os.remove(old)
            except OSError:
                pass
    return True


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so = _so_path()
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            log.warning("could not load %s: %s", so, e)
            return None
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        lib.ff_modinv_batch.argtypes = [i64p, i64p, ctypes.c_int64,
                                        ctypes.c_int64]
        lib.ff_lagrange_at_zero.argtypes = [i64p, i64p, ctypes.c_int64,
                                            ctypes.c_int64]
        lib.crc32c.argtypes = [u8p, ctypes.c_int64]
        lib.crc32c.restype = ctypes.c_uint32
        lib.lr_sgd_train.argtypes = [f32p, i32p, ctypes.c_int64,
                                     ctypes.c_int64, ctypes.c_int64, f32p,
                                     i64p, ctypes.c_int64, ctypes.c_int64,
                                     ctypes.c_double]
        lib.lr_sgd_train.restype = ctypes.c_double
        lib.cnn_sgd_train.argtypes = ([f32p, i32p]
                                      + [ctypes.c_int64] * 8  # n,H,W,Ci,C1,C2,Dh,K
                                      + [f32p, i64p, ctypes.c_int64,
                                         ctypes.c_int64, ctypes.c_double])
        lib.cnn_sgd_train.restype = ctypes.c_double
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


# ------------------------------------------------------------- finite field
def modinv_batch(x: np.ndarray, p: int) -> Optional[np.ndarray]:
    """Batch Fermat inverse mod p, or None when the native lib is absent."""
    lib = _load()
    if lib is None:
        return None
    flat = np.ascontiguousarray(np.asarray(x, np.int64).ravel())
    out = np.empty_like(flat)
    lib.ff_modinv_batch(flat, out, flat.size, p)
    return out.reshape(np.shape(x))


def lagrange_at_zero(points: np.ndarray, p: int) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    pts = np.ascontiguousarray(np.asarray(points, np.int64))
    lam = np.empty_like(pts)
    lib.ff_lagrange_at_zero(pts, lam, pts.size, p)
    return lam


def crc32c(data) -> Optional[int]:
    """CRC-32C of a bytes-like (bytes/bytearray/memoryview — zero-copy)."""
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, np.uint8)
    return int(lib.crc32c(np.ascontiguousarray(buf), buf.size))


# ------------------------------------------------------ native edge trainer
class NativeLRTrainer:
    """MobileNN-analog edge trainer: complete local SGD in C++, no jax.
    Drop-in for the EdgeClient `trainer` contract (train(params, round) ->
    (params, n_samples, metrics)); params cross the boundary as the flat
    [d*k + k] float32 vector the wire codec already ships."""

    def __init__(self, x: np.ndarray, y: np.ndarray, num_classes: int,
                 lr: float = 0.1, batch_size: int = 16, epochs: int = 1,
                 seed: int = 0):
        if not available():
            raise RuntimeError("native library unavailable (no g++?) — use "
                               "the jax SiloTrainer instead")
        self.x = np.ascontiguousarray(np.asarray(x, np.float32))
        self.y = np.ascontiguousarray(np.asarray(y, np.int32))
        self.k = int(num_classes)
        # the C++ kernel indexes logits[y[i]] unchecked — validate HERE so a
        # bad label is a python ValueError, not a native heap overrun
        if self.y.size and (self.y.min() < 0 or self.y.max() >= self.k):
            raise ValueError(
                f"labels must be in [0, {self.k}); got range "
                f"[{self.y.min()}, {self.y.max()}]")
        self.lr, self.bs, self.epochs, self.seed = lr, batch_size, epochs, seed
        self.n_samples = int(self.x.shape[0])

    def train(self, params_flat: np.ndarray, round_idx: int):
        lib = _load()
        n, d = self.x.shape
        bs = min(self.bs, n)
        nb = n // bs
        rs = np.random.RandomState(self.seed * 100003 + round_idx)
        perm = np.concatenate([
            rs.permutation(n)[: nb * bs] for _ in range(self.epochs)
        ]).astype(np.int64)
        out = np.ascontiguousarray(np.asarray(params_flat, np.float32).copy())
        mean_loss = lib.lr_sgd_train(
            self.x, self.y, n, d, self.k, out,
            np.ascontiguousarray(perm), self.epochs * nb, bs, self.lr)
        return out, self.n_samples, {"train_loss": float(mean_loss)}


class NativeCNNTrainer:
    """MobileNN-analog CNN edge trainer: the framework's 2-conv CNN
    (models/hub.py CNN) trained entirely in C++ — conv/pool/dense forward
    AND backward handwritten, no jax (reference:
    android/fedmlsdk/MobileNN/src/train/FedMLMNNTrainer.cpp:3-80 trains
    mnist/cifar CNNs on-device). Params cross the boundary as the flat
    float32 vector in jax.tree.leaves order of the flax CNN, so a global
    model from the TPU server trains here unchanged and aggregates back.

    x: [n, H, W, Cin] float32 (H, W divisible by 4); y: [n] int labels."""

    def __init__(self, x: np.ndarray, y: np.ndarray, num_classes: int,
                 c1: int = 32, c2: int = 64, hidden: int = 128,
                 lr: float = 0.1, batch_size: int = 16, epochs: int = 1,
                 seed: int = 0):
        if not available():
            raise RuntimeError("native library unavailable (no g++?) — use "
                               "the jax SiloTrainer instead")
        self.x = np.ascontiguousarray(np.asarray(x, np.float32))
        if self.x.ndim != 4:
            raise ValueError(f"x must be [n, H, W, Cin]; got {self.x.shape}")
        _n, h, w, _ci = self.x.shape
        if h % 4 or w % 4:
            raise ValueError(f"H, W must be divisible by 4 (two maxpool2 "
                             f"stages); got ({h}, {w})")
        self.y = np.ascontiguousarray(np.asarray(y, np.int32))
        self.k = int(num_classes)
        if self.y.size and (self.y.min() < 0 or self.y.max() >= self.k):
            raise ValueError(
                f"labels must be in [0, {self.k}); got range "
                f"[{self.y.min()}, {self.y.max()}]")
        self.c1, self.c2, self.hidden = int(c1), int(c2), int(hidden)
        self.lr, self.bs, self.epochs, self.seed = lr, batch_size, epochs, seed
        self.n_samples = int(self.x.shape[0])

    @property
    def n_params(self) -> int:
        _n, h, w, ci = self.x.shape
        f = (h // 4) * (w // 4) * self.c2
        return (self.c1 + 9 * ci * self.c1 + self.c2 + 9 * self.c1 * self.c2
                + self.hidden + f * self.hidden + self.k
                + self.hidden * self.k)

    def train(self, params_flat: np.ndarray, round_idx: int):
        lib = _load()
        n, h, w, ci = self.x.shape
        out = np.ascontiguousarray(np.asarray(params_flat, np.float32).copy())
        if out.size != self.n_params:
            raise ValueError(f"params size {out.size} != expected "
                             f"{self.n_params} for this architecture")
        bs = min(self.bs, n)
        nb = n // bs
        rs = np.random.RandomState(self.seed * 100003 + round_idx)
        perm = np.concatenate([
            rs.permutation(n)[: nb * bs] for _ in range(self.epochs)
        ]).astype(np.int64)
        mean_loss = lib.cnn_sgd_train(
            self.x, self.y, n, h, w, ci, self.c1, self.c2, self.hidden,
            self.k, out, np.ascontiguousarray(perm), self.epochs * nb, bs,
            self.lr)
        return out, self.n_samples, {"train_loss": float(mean_loss)}
