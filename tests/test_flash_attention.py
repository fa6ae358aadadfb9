"""Flash-attention backend numerics — needs a real TPU backend (the CPU test
mesh uses the dense path; the kernel itself is Pallas TPU-only).

Under pytest these SKIP: tests/conftest.py pins the CPU platform before any
test module imports, so ``jax.default_backend()`` is ``'cpu'`` here.  To run
the numerics against the chip, execute the file directly (no conftest):

    python tests/test_flash_attention.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # direct run

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.ops.ring_attention import _flash_eligible, local_attention

tpu_only = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="flash kernel needs a TPU backend")


def _rand(shape, key):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


@tpu_only
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    B, T, H, D = 2, 256, 4, 64
    q, k, v = (_rand((B, T, H, D), i) for i in range(3))
    dense = local_attention(q, k, v, causal=causal, backend="dense")
    flash = local_attention(q, k, v, causal=causal, backend="flash")
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(dense), atol=2e-2, rtol=2e-2)


@tpu_only
def test_flash_grads_match_dense():
    B, T, H, D = 1, 128, 2, 64
    q, k, v = (_rand((B, T, H, D), i) for i in range(3))

    def loss(backend):
        def f(q, k, v):
            return jnp.sum(local_attention(q, k, v, causal=True,
                                           backend=backend) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    gd, gf = loss("dense"), loss("flash")
    for a, b in zip(gd, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-2, rtol=5e-2)


def test_eligibility_gate():
    q = jnp.zeros((1, 256, 2, 64))
    k = jnp.zeros((1, 256, 2, 64))
    on_tpu = jax.default_backend() == "tpu"
    assert _flash_eligible(q, k, True, 0, 0) == on_tpu
    # traced/unequal offsets, short or ragged T: never eligible
    assert not _flash_eligible(q, k, True, 0, 128)          # shifted causal
    assert not _flash_eligible(q, k, True, jnp.zeros(()), 0)  # traced offset
    assert not _flash_eligible(q[:, :96], k[:, :96], False, 0, 0)  # T % 128
    assert not _flash_eligible(q, k[:, :128], False, 0, 0)  # Tq != Tk


def test_forced_flash_on_ineligible_raises():
    q = k = v = jnp.zeros((1, 256, 2, 64))
    with pytest.raises(ValueError, match="flash"):
        # shifted causal offsets are never flash-eligible, on any backend
        local_attention(q, k, v, causal=True, q_offset=0, k_offset=128,
                        backend="flash")


if __name__ == "__main__":
    # direct execution path — real chip, no conftest CPU pin
    test_eligibility_gate()
    test_forced_flash_on_ineligible_raises()
    for c in (False, True):
        test_flash_matches_dense(c)
    test_flash_grads_match_dense()
    print("OK (flash numerics verified on", jax.default_backend(), ")")
