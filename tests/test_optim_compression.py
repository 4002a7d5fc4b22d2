"""Optimizers + gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.optim import adamw, adafactor, sgd, constant, clip_by_global_norm
from repro.runtime.compression import (compress_with_error_feedback,
                                       int8_compress, int8_decompress)


def _quadratic_losses(opt, steps=60):
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    losses = []
    for i in range(steps):
        g = jax.grad(loss)(params)
        params, state = opt.update(jnp.asarray(i), state, params, g)
        losses.append(float(loss(params)))
    return losses


@pytest.mark.parametrize("make", [
    lambda: adamw(constant(0.1)),
    lambda: adamw(constant(0.1), state_dtype=jnp.bfloat16),
    lambda: sgd(constant(0.05)),
    lambda: adafactor(constant(0.5)),
])
def test_optimizers_converge(make):
    losses = _quadratic_losses(make())
    assert losses[-1] < losses[0] * 0.05


def test_grad_clip():
    g = {"a": jnp.asarray([3.0, 4.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(5.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0)


def test_bf16_states_halve_memory():
    p = {"w": jnp.zeros((64, 64), jnp.float32)}
    s32 = adamw(constant(1e-3)).init(p)
    s16 = adamw(constant(1e-3), state_dtype=jnp.bfloat16).init(p)
    assert s16["m"]["w"].dtype == jnp.bfloat16
    assert s16["m"]["w"].nbytes * 2 == s32["m"]["w"].nbytes


# ---- compression ------------------------------------------------------------

@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_int8_roundtrip_error_bound(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((8, 32)) * 10, jnp.float32)
    q, scale = int8_compress(x)
    back = int8_decompress(q, scale)
    amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    # error per element ≤ half a quantization step
    assert np.all(np.abs(np.asarray(back - x)) <= amax / 127.0 * 0.51 + 1e-6)


def test_error_feedback_recovers_signal():
    """Repeatedly compressing the SAME gradient with error feedback must
    sum to the true gradient over time (the EF guarantee)."""
    g = jnp.asarray(np.linspace(-1e-3, 1e-3, 64).reshape(1, 64), jnp.float32)
    err = jnp.zeros_like(g)
    acc = jnp.zeros_like(g)
    for _ in range(50):
        q, scale, err = compress_with_error_feedback(g, err)
        acc = acc + int8_decompress(q, scale)
    np.testing.assert_allclose(np.asarray(acc / 50), np.asarray(g),
                               atol=float(jnp.abs(g).max()) * 0.02)


def test_compressed_allreduce_single_device_mesh():
    from repro.launch.mesh import make_mesh
    from repro.runtime.compression import compressed_grad_allreduce
    mesh = make_mesh((1,), ("pod",))
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((4, 8)),
                          jnp.float32)}
    err = {"w": jnp.zeros((4, 8), jnp.float32)}
    out, new_err = compressed_grad_allreduce(g, err, mesh, "pod")
    # 1-device psum = dequantized value; error = quantization residual
    np.testing.assert_allclose(np.asarray(out["w"] + new_err["w"]),
                               np.asarray(g["w"]), atol=1e-5)
