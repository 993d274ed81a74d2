"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed in interpret mode on CPU (TPU is the lowering target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.kmeans_assign.ops import assign_with_dist
from repro.kernels.kmeans_assign.ref import assign_ref
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_reference


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (b, s, h, kv, d, window, dtype)
    (1, 128, 4, 4, 64, 0, jnp.float32),
    (2, 256, 4, 2, 64, 0, jnp.float32),
    (1, 256, 8, 1, 64, 0, jnp.float32),      # MQA
    (1, 128, 4, 4, 128, 0, jnp.float32),
    (1, 128, 2, 2, 256, 0, jnp.float32),     # gemma head_dim
    (2, 256, 4, 2, 64, 128, jnp.float32),    # sliding window
    (1, 256, 4, 4, 64, 64, jnp.float32),     # small window
    (1, 128, 4, 2, 64, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("b,s,h,kv,d,window,dtype", FLASH_CASES)
def test_flash_attention_vs_ref(b, s, h, kv, d, window, dtype):
    ks = jax.random.split(jax.random.key(s + h + d + window), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
    out = flash_attention(q, k, v, True, window, True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_grad_routes_through_oracle():
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 0, True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(attention_ref(q, k, v) ** 2)

    g = jax.grad(f)(q, k, v)
    g_ref = jax.grad(f_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    # (b, s, h, p, n, chunk, dtype)
    (2, 128, 4, 32, 16, 32, jnp.float32),
    (1, 256, 2, 64, 128, 128, jnp.float32),
    (1, 64, 8, 64, 64, 32, jnp.float32),
    (2, 128, 2, 128, 128, 64, jnp.float32),  # jamba head_dim
    (1, 128, 4, 32, 16, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("b,s,h,p,n,chunk,dtype", SSD_CASES)
def test_ssd_vs_ref(b, s, h, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.key(s * h + p), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(dtype)
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (h,)))
    da = (dt.astype(jnp.float32) * a).astype(jnp.float32)
    bm = jax.random.normal(ks[3], (b, s, n), dtype)
    cm = jax.random.normal(ks[4], (b, s, n), dtype)
    xs = (x.astype(jnp.float32) * dt[..., None].astype(jnp.float32)
          ).astype(dtype)
    y, state = ssd(xs, da, bm, cm, chunk, True)
    y_ref, state_ref = ssd_reference(xs, da, bm, cm, chunk)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_ref),
                               atol=tol, rtol=tol)


def test_ssd_state_matches_recurrence():
    """Chunked SSD final state == step-by-step recurrence."""
    from repro.models.mamba2 import ssd_recurrent_step
    b, s, h, p, n = 1, 64, 2, 16, 8
    ks = jax.random.split(jax.random.key(7), 4)
    x = jax.random.normal(ks[0], (b, s, h, p))
    da = -jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    bm = jax.random.normal(ks[2], (b, s, n))
    cm = jax.random.normal(ks[3], (b, s, n))
    _, state_chunked = ssd_reference(x, da, bm, cm, 16)
    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        y_t, state = ssd_recurrent_step(state, x[:, t], da[:, t], bm[:, t],
                                        cm[:, t])
        ys.append(y_t)
    np.testing.assert_allclose(np.asarray(state_chunked),
                               np.asarray(state), atol=1e-4, rtol=1e-4)
    # outputs of the dual form match the recurrence too
    y_chunked, _ = ssd_reference(x, da, bm, cm, 16)
    np.testing.assert_allclose(np.asarray(y_chunked),
                               np.asarray(jnp.stack(ys, axis=1)),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# kmeans assign
# ---------------------------------------------------------------------------

KM_CASES = [
    (100, 8, 3, jnp.float32),
    (1000, 64, 3, jnp.float32),
    (513, 59, 8, jnp.float32),       # wafer dims, non-multiple of block
    (256, 16, 32, jnp.float32),
    (300, 64, 3, jnp.bfloat16),
]


@pytest.mark.parametrize("n,d,k,dtype", KM_CASES)
def test_kmeans_assign_vs_ref(n, d, k, dtype):
    ks = jax.random.split(jax.random.key(n + d + k), 2)
    x = jax.random.normal(ks[0], (n, d), dtype)
    c = jax.random.normal(ks[1], (k, d), dtype)
    a, d2 = assign_with_dist(x, c, interpret=True)
    a_ref, d2_ref = assign_ref(x, c)
    # bf16 rounding can flip genuinely-tied assignments; compare distances
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2_ref),
                               atol=1e-2, rtol=1e-2)
    if dtype == jnp.float32:
        assert (np.asarray(a) == np.asarray(a_ref)).mean() > 0.999


def test_interpret_default_native_on_tpu_interpreted_on_cpu_only(
        monkeypatch):
    """The interpreter is the CPU test path only: on TPU kernels run
    natively, and any other backend is an error rather than a kernel
    quietly interpreted on an accelerator."""
    from repro.kernels import interpret_default
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert interpret_default() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        interpret_default()
