"""Flash-attention backend numerics.

The ``tpu_only`` tests need a real TPU backend (the CPU test mesh uses the
dense path).  Under pytest they SKIP: tests/conftest.py pins the CPU platform
before any test module imports, so ``jax.default_backend()`` is ``'cpu'``
here.  To run the numerics against the chip, execute the file directly (no
conftest):

    python tests/test_flash_attention.py

The ``interpret`` tests run the same splash kernel in the Pallas interpreter
on the CPU, at a shape small enough for tier-1.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # direct run

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.ops.ring_attention import (
    _flash_eligible, _splash_attention, _splash_kernel, local_attention)

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


# T = 384 tiles as 3 x 3 blocks of 128 (256 does not divide it): the kernels
# skip the blocks above the diagonal, mask the ones on it, and the fused
# backward sums three dq partials
_INTERPRET_SHAPE = (1, 384, 2, 64)
_INTERPRET_TOL = {jnp.float32: (1e-4, 1e-4), jnp.bfloat16: (2e-2, 5e-2)}


def _interpret_vs_dense(causal, dtype, scale, fn):
    q, k, v = (_rand(_INTERPRET_SHAPE, i).astype(dtype) for i in range(3))
    splash = fn(lambda q, k, v: _splash_attention(
        q, k, v, causal=causal, scale=scale, interpret=True))(q, k, v)
    dense = fn(lambda q, k, v: local_attention(
        q, k, v, causal=causal, scale=scale, backend="dense"))(q, k, v)
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x, np.float32), (splash, dense))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_interpret_forward_matches_dense(causal, dtype):
    splash, dense = _interpret_vs_dense(causal, dtype, 0.125, jax.jit)
    tol = _INTERPRET_TOL[dtype][0]
    np.testing.assert_allclose(splash, dense, atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_interpret_grads_match_dense(causal, dtype):
    def grads(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    splash, dense = _interpret_vs_dense(causal, dtype, 0.125, grads)
    tol = _INTERPRET_TOL[dtype][1]
    for name, a, b in zip("qkv", splash, dense):
        # a share of the largest entry, as chip_smoke.py's flash step reads it
        err = np.abs(a - b).max() / np.abs(b).max()
        assert err < tol, f"d{name} off dense by {err:.3g} of max"


def test_interpret_scale_applied_once():
    """The kernel takes no scale: it is folded into q. Twice, or not at all,
    and a scale other than the default no longer agrees with the dense path."""
    splash, dense = _interpret_vs_dense(True, jnp.float32, 0.4, jax.jit)
    np.testing.assert_allclose(splash, dense, atol=1e-4, rtol=1e-4)


def test_splash_kernel_is_cached():
    """One kernel (and one host-side mask computation) per shape, also when
    the first call happens under a trace."""
    _splash_kernel.cache_clear()
    made = []
    jax.jit(lambda: made.append(_splash_kernel(256, 2, "causal", True)) or 0)()
    assert _splash_kernel(256, 2, "causal", True) is made[0]
    assert _splash_kernel(256, 2, "full", True) is not made[0]
    assert _splash_kernel.cache_info().misses == 2
    # built under a trace, yet no tracer is kept: the mask info is concrete
    for leaf in jax.tree_util.tree_leaves(made[0]):
        assert not isinstance(leaf, jax.core.Tracer)


def test_kernel_names_match_the_benchmarks_flash_patterns():
    """A trace names a Pallas kernel by its ``pallas_call`` name; the accepted
    ``flash_ms_per_step`` / ``flash_roofline`` find the attention kernels by
    the prefixes in their metric file, forward and backward."""
    import json
    import os
    import re

    spec = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                        "metrics", "flash_roofline.json")
    with open(spec) as f:
        forward, backward = json.load(f)["params"]["patterns"]
    q, k, v = (_rand((1, 256, 2, 64), i) for i in range(3))

    def loss(q, k, v):
        return _splash_attention(q, k, v, causal=True, scale=0.125,
                                 interpret=True).sum()

    names = set(re.findall(r"\bname=(\w+)", str(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))))
    kernels = {n for n in names if "splash_mha_" in n}
    assert len(kernels) == 2, names
    assert sum(bool(re.search(forward, n)) for n in kernels) == 1, kernels
    assert sum(bool(re.search(backward, n)) for n in kernels) == 1, kernels


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
