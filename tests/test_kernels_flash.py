"""Flash-attention Pallas kernel vs oracle: shape/dtype/feature sweeps in
interpret mode (the per-kernel allclose deliverable)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def _mk(B, Sq, Skv, H, KVH, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Skv, KVH, D), dtype)
    v = jax.random.normal(ks[2], (B, Skv, KVH, D), dtype)
    return q, k, v


@pytest.mark.parametrize("shape", [
    (1, 128, 128, 4, 4, 64),    # MHA
    (2, 256, 256, 8, 2, 64),    # GQA 4:1
    (1, 192, 320, 4, 2, 128),   # ragged (padding path), cross lengths
    (1, 128, 128, 2, 1, 256),   # gemma2-style head_dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref(shape, dtype):
    B, Sq, Skv, H, KVH, D = shape
    q, k, v = _mk(B, Sq, Skv, H, KVH, D, dtype)
    out_k = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64,
                            interpret=True)
    out_r = attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (64, 0.0),
                                            (0, 50.0), (32, 30.0)])
def test_flash_window_softcap(window, softcap):
    q, k, v = _mk(1, 128, 128, 4, 2, 64, jnp.float32, seed=3)
    out_k = flash_attention(q, k, v, causal=True, window=window,
                            softcap=softcap, block_q=32, block_kv=32,
                            interpret=True)
    out_r = attention_ref(q, k, v, causal=True, window=window,
                          softcap=softcap)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)


def test_flash_noncausal():
    q, k, v = _mk(1, 64, 96, 2, 2, 64, jnp.float32, seed=5)
    out_k = flash_attention(q, k, v, causal=False, block_q=32, block_kv=32,
                            interpret=True)
    out_r = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)


def test_flash_matches_model_attention():
    """The kernel computes the same function as the model's blockwise
    context-parallel formulation (transformer._blockwise_traced_window)."""
    from repro.models.transformer import _blockwise_traced_window
    q, k, v = _mk(2, 128, 128, 4, 2, 64, jnp.float32, seed=7)
    out_k = flash_attention(q, k, v, causal=True, window=32,
                            block_q=64, block_kv=64,
                            interpret=True)
    out_m = _blockwise_traced_window(q, k, v, jnp.int32(32), jnp.int32(0),
                                     softcap=0.0, block_q=64, block_kv=64)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_m),
                               rtol=2e-5, atol=2e-5)
