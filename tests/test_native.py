"""Native C++ tier (reference analog: android/fedmlsdk/MobileNN/ — the
C++ edge trainer + C++ secagg kernels). The .so compiles on first use;
kernels must agree exactly with the numpy/python implementations."""
import binascii

import numpy as np
import pytest

from fedml_tpu.mpc.finite import DEFAULT_PRIME, modular_inv, shamir_reconstruct, shamir_share
from fedml_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no native toolchain (g++) available")


def test_modinv_batch_matches_python():
    rs = np.random.RandomState(0)
    x = rs.randint(1, DEFAULT_PRIME, size=200).astype(np.int64)
    out = native.modinv_batch(x, DEFAULT_PRIME)
    ref = np.array([pow(int(v), DEFAULT_PRIME - 2, DEFAULT_PRIME)
                    for v in x], np.int64)
    np.testing.assert_array_equal(out, ref)
    # and they really are inverses
    np.testing.assert_array_equal(
        (x.astype(object) * out.astype(object)) % DEFAULT_PRIME, 1)


def test_modular_inv_uses_native_and_matches():
    x = np.arange(1, 50, dtype=np.int64)
    out = modular_inv(x)
    np.testing.assert_array_equal(
        (x.astype(object) * np.asarray(out).astype(object)) % DEFAULT_PRIME, 1)


def test_lagrange_at_zero_matches_reconstruction():
    """Native Lagrange coefficients reproduce Shamir reconstruction."""
    rs = np.random.default_rng(1)
    secret = np.array([123456789, 42], np.int64)
    shares = shamir_share(secret, n=5, t=2, rng=rs)
    holders = [0, 2, 4]
    ref = shamir_reconstruct(shares[holders], holders)
    lam = native.lagrange_at_zero(
        np.asarray([h + 1 for h in holders], np.int64), DEFAULT_PRIME)
    acc = np.zeros_like(secret)
    for li, h in zip(lam, holders):
        acc = (acc + int(li) * shares[h].astype(object)) % DEFAULT_PRIME
    np.testing.assert_array_equal(acc.astype(np.int64), ref)
    np.testing.assert_array_equal(ref, secret)


def test_crc32c_known_vector():
    # standard CRC-32C test vector: "123456789" -> 0xE3069283
    assert native.crc32c(b"123456789") == 0xE3069283


def test_wire_frame_crc_detects_corruption():
    """The codec appends a CRC-32C trailer when native is available; a
    flipped payload byte must raise instead of decoding wrong tensors."""
    from fedml_tpu.comm.serialization import decode, encode

    frame = bytearray(encode({"w": np.arange(64, dtype=np.float32)}))
    assert frame[-8:-4] == b"C32C"
    decode(bytes(frame))  # intact frame decodes
    frame[20] ^= 0xFF     # corrupt one payload byte
    with pytest.raises(ValueError, match="CRC mismatch"):
        decode(bytes(frame))


def test_native_lr_trainer_learns_and_matches_contract():
    rs = np.random.RandomState(0)
    n, d, k = 256, 8, 3
    w_true = rs.randn(d, k)
    x = rs.randn(n, d).astype(np.float32)
    y = np.argmax(x @ w_true, axis=1).astype(np.int32)
    tr = native.NativeLRTrainer(x, y, num_classes=k, lr=0.3, batch_size=32,
                                epochs=2, seed=7)
    params = np.zeros(d * k + k, np.float32)
    losses = []
    for r in range(6):
        params, n_samp, m = tr.train(params, r)
        losses.append(m["train_loss"])
    assert n_samp == n
    assert losses[-1] < losses[0] * 0.5, losses
    # accuracy of the C++-trained model, computed in numpy
    W = params[: d * k].reshape(d, k)
    b = params[d * k:]
    acc = (np.argmax(x @ W + b, axis=1) == y).mean()
    assert acc > 0.9, acc


def test_native_trainer_in_cross_device_round():
    """The C++ trainer rides the cross-device runtime via a flat-vector
    adapter — the MobileNN-client shape: native engine + message layer."""
    import uuid

    from fedml_tpu.comm import FedCommManager
    from fedml_tpu.comm.loopback import LoopbackTransport, release_router
    from fedml_tpu.cross_device import CrossDeviceServer, EdgeClient

    rs = np.random.RandomState(1)
    d, k = 8, 3
    w_true = rs.randn(d, k)

    class FlatAdapter:
        """EdgeClient speaks pytrees; the native engine speaks flat vectors."""

        def __init__(self, inner):
            self.inner = inner
            self.n_samples = inner.n_samples

        def train(self, params, round_idx):
            flat = np.concatenate([
                np.asarray(params["w"], np.float32).ravel(),
                np.asarray(params["b"], np.float32).ravel()])
            out, n, m = self.inner.train(flat, round_idx)
            return ({"w": out[: d * k].reshape(d, k), "b": out[d * k:]},
                    n, m)

    run_id = f"native-{uuid.uuid4().hex[:6]}"
    init = {"w": np.zeros((d, k), np.float32), "b": np.zeros(k, np.float32)}
    server = CrossDeviceServer(
        FedCommManager(LoopbackTransport(0, run_id), 0),
        init_params=init, num_rounds=3, devices_per_round=2, min_devices=2,
        round_timeout=30.0)
    clients = []
    for did in (1, 2):
        x = rs.randn(128, d).astype(np.float32)
        y = np.argmax(x @ w_true, axis=1).astype(np.int32)
        tr = FlatAdapter(native.NativeLRTrainer(
            x, y, num_classes=k, lr=0.3, batch_size=32, seed=did))
        clients.append(EdgeClient(
            FedCommManager(LoopbackTransport(did, run_id), did), did, tr))
    server.run(background=True)
    for c in clients:
        c.run(background=True)
    for c in clients:
        c.register()
    assert server.done.wait(timeout=60)
    release_router(run_id)
    assert len(server.history) == 3
    # the federated native model classifies well
    x = rs.randn(200, d).astype(np.float32)
    y = np.argmax(x @ w_true, axis=1)
    W, b = server.params["w"], server.params["b"]
    assert (np.argmax(x @ W + b, axis=1) == y).mean() > 0.85


def test_native_cnn_trainer_matches_flax_gradients():
    """The C++ CNN backward must reproduce the flax CNN's SGD step on the
    SAME flat params (jax.tree.leaves order) — full-batch, one step,
    elementwise comparison (reference analog:
    android/fedmlsdk/MobileNN/src/train/FedMLMNNTrainer.cpp on-device CNN)."""
    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.cross_silo.secagg_manager import flatten_params
    from fedml_tpu.models import hub

    rs = np.random.RandomState(0)
    n, H, W, Ci, K = 32, 8, 8, 1, 10
    x = rs.randn(n, H, W, Ci).astype(np.float32)
    y = rs.randint(0, K, n)
    model = hub.create("cnn", K)
    params = hub.init_params(model, (H, W, Ci), jax.random.key(0))
    flat = flatten_params(params).astype(np.float32)

    tr = native.NativeCNNTrainer(x, y, K, lr=0.1, batch_size=n, epochs=1)
    assert tr.n_params == flat.size
    out, n_samp, m = tr.train(flat, 0)
    assert n_samp == n

    def loss_fn(p):
        logits = model.apply({"params": p}, jnp.asarray(x))
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.asarray(y)).mean()

    loss, grads = jax.value_and_grad(loss_fn)(params)
    ref = flatten_params(
        jax.tree.map(lambda a, g: a - 0.1 * g, params, grads)
    ).astype(np.float32)
    assert abs(m["train_loss"] - float(loss)) < 1e-3
    # measured max |delta| is ~3e-8 on CPU; 1e-6 leaves platform headroom
    # while actually enforcing the README/COVERAGE precision claim
    # (round-3 advisor: the old 5e-4 bound enforced nothing)
    np.testing.assert_allclose(out, ref, atol=1e-6)


def test_native_cnn_trainer_learns_digits():
    from sklearn.datasets import load_digits

    d = load_digits()
    x = (d.data.astype(np.float32) / 16.0).reshape(-1, 8, 8, 1)[:512]
    y = d.target.astype(np.int32)[:512]
    import jax

    from fedml_tpu.cross_silo.secagg_manager import flatten_params
    from fedml_tpu.models import hub

    tr = native.NativeCNNTrainer(x, y, 10, lr=0.2, batch_size=32, epochs=1,
                                 seed=3)
    # fan-in-scaled init from the flax CNN (a flat gaussian init stalls)
    params = flatten_params(hub.init_params(
        hub.create("cnn", 10), (8, 8, 1), jax.random.key(0))
    ).astype(np.float32)
    assert params.size == tr.n_params
    losses = []
    for r in range(8):
        params, _n, m = tr.train(params, r)
        losses.append(m["train_loss"])
    assert losses[-1] < losses[0] * 0.5, losses


def test_native_cnn_rejects_bad_shapes():
    x = np.zeros((4, 6, 6, 1), np.float32)  # 6 % 4 != 0
    with pytest.raises(ValueError, match="divisible by 4"):
        native.NativeCNNTrainer(x, np.zeros(4, np.int32), 3)
    x = np.zeros((4, 8, 8, 1), np.float32)
    with pytest.raises(ValueError, match="labels"):
        native.NativeCNNTrainer(x, np.full(4, 9, np.int32), 3)


def test_build_is_keyed_on_source_hash_not_file_times(tmp_path, monkeypatch):
    """A binary built from OTHER source must never be loaded, however new
    its file time says it is (a copied tree scrambles times): the loaded
    library's NAME carries the hash of fedml_native.cpp, so a foreign or
    stale .so — here garbage under the old fixed name and under another
    hash — is simply not the file that gets opened."""
    import hashlib
    import os

    src = open(native._SRC, "rb").read()
    so = native._so_path()
    assert hashlib.sha256(src).hexdigest()[:16] in os.path.basename(so)
    assert os.path.exists(so)          # available() above built or found it
    strays = [os.path.join(native._HERE, "libfedml_native.so"),
              os.path.join(native._HERE, "libfedml_native.0123456789abcdef.so")]
    try:
        for s in strays:
            with open(s, "wb") as f:
                f.write(b"not an ELF")
            os.utime(s, (2e9, 2e9))    # "newer" than everything
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", False)
        assert native.available()
        assert native.crc32c(b"123456789") == 0xE3069283
    finally:
        for s in strays:
            if os.path.exists(s):
                os.remove(s)
