"""AdamW unit tests: convergence, schedule, int8 moment quantization,
error-feedback gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st

from repro.optim import adamw


def _quad_problem():
    target = jnp.asarray([1.5, -2.0, 0.5, 3.0])
    params = {"w": jnp.zeros((4,)), "b": jnp.zeros(())}

    def loss_fn(p):
        return jnp.sum((p["w"] - target) ** 2) + (p["b"] - 1.0) ** 2
    return params, loss_fn, target


@pytest.mark.parametrize("quant", [False, True], ids=["fp32", "int8"])
def test_adamw_converges_on_quadratic(quant):
    cfg = adamw.AdamWConfig(lr=0.05, weight_decay=0.0, warmup_steps=0,
                            total_steps=400, min_lr_frac=1.0,
                            quantize_moments=quant)
    params, loss_fn, target = _quad_problem()
    state = adamw.init(cfg, params)
    for _ in range(400):
        grads = jax.grad(loss_fn)(params)
        params, state, _ = adamw.update(cfg, grads, state, params)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=0.05)
    assert abs(float(params["b"]) - 1.0) < 0.05


def test_schedule_shape():
    cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=100,
                            min_lr_frac=0.1)
    lrs = [float(adamw.lr_schedule(cfg, jnp.asarray(s)))
           for s in range(100)]
    assert lrs[0] < lrs[9] <= 1e-3 + 1e-9          # warmup rises
    assert abs(lrs[10] - 1e-3) < 1e-4              # peak after warmup
    assert lrs[-1] < 0.2 * 1e-3                    # decays toward min
    assert lrs[-1] >= 0.1 * 1e-3 - 1e-9            # respects floor


def test_quantized_states_are_small_and_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (513, 300)) * 0.01
    q, s = adamw._quantize(x)
    assert q.dtype == jnp.int8
    assert q.shape == (513, 384)                   # padded to 128
    back = adamw._dequantize(q, s, x.shape, x.size)
    assert back.shape == x.shape
    # blockwise absmax int8: relative error bounded by ~1/127 per block
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               atol=float(jnp.max(jnp.abs(x))) / 100)


def test_grad_compression_error_feedback_is_unbiased():
    """Sum of compressed grads ~ sum of true grads (residual carries)."""
    rng = jax.random.PRNGKey(1)
    residual = jnp.zeros((256,))
    total_true = jnp.zeros((256,))
    total_hat = jnp.zeros((256,))
    for i in range(50):
        rng, k = jax.random.split(rng)
        g = jax.random.normal(k, (256,)) * 0.1
        g_hat, residual = adamw.compress_decompress(g, residual)
        total_true += g
        total_hat += g_hat
    # residual is bounded; accumulated estimates track the true sum
    err = float(jnp.max(jnp.abs(total_true - (total_hat + residual))))
    assert err < 1e-4


@given(st.integers(min_value=1, max_value=400),
       st.integers(min_value=1, max_value=5))
@settings(max_examples=20, deadline=None)
def test_property_quantize_roundtrip_shape(n, m):
    x = jnp.linspace(-1.0, 1.0, n * m).reshape(n, m)
    q, s = adamw._quantize(x)
    back = adamw._dequantize(q, s, x.shape, x.size)
    assert back.shape == x.shape
    assert float(jnp.max(jnp.abs(back - x))) <= 2.0 / 127 + 1e-6
