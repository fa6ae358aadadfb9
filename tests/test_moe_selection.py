"""The routers' selection (``ops/moe.py::_top_k``): the Pallas kernel
``bf_moe_select``, run here in the Pallas interpreter, against the sorted
form (``lax.top_k`` and ``take_along_axis``) it has to equal to the bit —
ids, weights and gradients, at the router settings of the four cells with
routed experts and at tiny widths; the tie rule and the order; the rule that
picks the form; the counter that says the kernel ran; and
``benchmarks/router_select_bench.py`` at its tiny shape."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from bluefog_tpu.ops import moe

# experts, top_k, groups, groups kept, router, scale, eps: the cells' own
# settings (joyai, ling3flash, lfm2moe, smallthinker)
ROUTERS = {
    "latent_moe": (256, 8, 1, 1, "sigmoid", 2.5, 0.0),
    "linear_latent_moe": (512, 8, 8, 4, "sigmoid", 2.5, 0.0),
    "conv_gqa_moe": (32, 4, 1, 1, "sigmoid", 1.0, 1e-6),
    "gqa_moe": (64, 6, 1, 1, "softmax", 1.0, 0.0),
}
TOKENS, WIDTH = 256, 48


@pytest.fixture
def form(monkeypatch):
    """``form(name)`` makes ``ops/moe.py`` take that form of the selection
    whatever the backend and the shape."""
    def ask(name):
        monkeypatch.setattr(moe, "_select_form", lambda *a, **k: name)
    return ask


def rand(shape, seed, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def route(family, x, w, bias):
    e, k, n_group, topk_group, kind, scale, eps = ROUTERS[family]
    if kind == "softmax":
        return moe.softmax_topk_router(x, w, top_k=k)
    return moe.sigmoid_topk_router(x, w, bias, top_k=k, scale=scale,
                                   n_group=n_group, topk_group=topk_group,
                                   eps=eps)


def routed(family, seed=0):
    """One jitted router call with the gradients of a probe-weighted sum of
    its weights by ``x``, the router's kernel and the selection bias."""
    e, k = ROUTERS[family][:2]
    x = rand((TOKENS, WIDTH), seed, jnp.bfloat16)
    w = rand((WIDTH, e), seed + 1) * WIDTH ** -0.5
    bias = rand((e,), seed + 2) * 0.05
    probe = rand((TOKENS, k), seed + 3)

    def total(x, w, bias):
        idx, weights = route(family, x, w, bias)
        return jnp.sum(weights * probe), (idx, weights)

    def call():
        (_, (idx, weights)), grads = jax.jit(jax.value_and_grad(
            total, argnums=(0, 1, 2), has_aux=True))(x, w, bias)
        return {"idx": idx, "weights": weights, "d_x": grads[0],
                "d_router": grads[1], "d_bias": grads[2]}
    return call


def bits(a):
    a = np.asarray(a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a)
    return a.view(np.int32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("what", ["idx", "weights", "d_x", "d_router",
                                  "d_bias"])
@pytest.mark.parametrize("family", sorted(ROUTERS))
def test_the_kernel_equals_the_sorted_form_to_the_bit(family, what, form):
    call = routed(family)
    form("sorted")
    want = call()
    form("kernel_interpret")
    got = call()
    assert got[what].shape == want[what].shape
    assert got[what].dtype == want[what].dtype
    assert np.array_equal(bits(got[what]), bits(want[what]))
    if what == "idx":
        assert got["idx"].dtype == jnp.int32
        assert int(got["idx"].min()) >= 0
        assert int(got["idx"].max()) < ROUTERS[family][0]
    if what == "d_bias":        # the bias steers and takes no gradient
        assert not np.asarray(got["d_bias"]).any()
    if what in ("d_x", "d_router"):
        assert np.asarray(got[what].astype(jnp.float32)).any()


@pytest.mark.parametrize("family", sorted(ROUTERS))
def test_the_kernel_form_sorts_gathers_and_scatters_nothing(family, form):
    """Compiled for the CPU, the sorted form holds a sort (or a top-k call)
    and the kernel form neither that nor a gather nor a scatter."""
    def text():
        e, k = ROUTERS[family][:2]
        x, w = rand((TOKENS, WIDTH), 0, jnp.bfloat16), rand((WIDTH, e), 1)
        return jax.jit(jax.grad(lambda x, w: jnp.sum(route(
            family, x, w, jnp.zeros((e,)))[1] ** 2), argnums=(0, 1))).lower(
                x, w).as_text()
    form("kernel_interpret")
    kernel = text()
    for op in ("sort", "top_k", "topk", "gather", "scatter"):
        assert f"stablehlo.{op}" not in kernel and f"chlo.{op}" not in kernel
    form("sorted")
    assert "top_k" in text()


def both_forms(form, scores, k, values=None, **groups):
    out = []
    for name in ("sorted", "kernel_interpret"):
        form(name)
        out.append(jax.jit(lambda s, v: moe._top_k(
            s, k, v, **groups))(scores, values))
    (idx, chosen), (idx_k, chosen_k) = out
    assert np.array_equal(np.asarray(idx), np.asarray(idx_k))
    assert np.array_equal(bits(chosen), bits(chosen_k))
    return np.asarray(idx_k), np.asarray(chosen_k)


@pytest.mark.parametrize("own_values", [True, False],
                         ids=["own_values", "other_values"])
def test_a_tie_goes_to_the_lower_index_and_the_order_is_descending(
        own_values, form):
    """Scores drawn from five distinct numbers, so every row is ties."""
    scores = jnp.round(rand((128, 40), 0) * 2) / 2
    values = None if own_values else rand((128, 40), 1)
    idx, chosen = both_forms(form, scores, 6, values)
    s = np.asarray(scores)
    picked = np.take_along_axis(s, idx, axis=-1)
    assert (np.diff(picked, axis=-1) <= 0).all()             # descending
    same = np.diff(picked, axis=-1) == 0
    assert same.any() and (np.diff(idx, axis=-1)[same] > 0).all()
    # nothing left out beats the last chosen, and an equal one lies after it
    for row in range(128):
        rest = np.setdiff1d(np.arange(40), idx[row])
        assert (s[row, rest] <= picked[row, -1]).all()
        assert (rest[s[row, rest] == picked[row, -1]] > idx[row, -1]).all()
    want = picked if own_values else np.take_along_axis(
        np.asarray(values), idx, axis=-1)
    assert np.array_equal(bits(jnp.asarray(chosen)), bits(jnp.asarray(want)))


def test_tied_groups_go_to_the_lower_group(form):
    """Every group scores the same: the first ``topk_group`` are kept and
    the chosen are their first experts."""
    scores = jnp.ones((128, 64), jnp.float32)
    idx, _ = both_forms(form, scores, 4, n_group=8, topk_group=2)
    assert (idx == np.arange(4)).all()
    # one group's two best lift it over the tie: it is kept, then group 0
    scores = scores.at[:, 41].set(2.0)
    idx, _ = both_forms(form, scores, 4, n_group=8, topk_group=2)
    assert (idx == np.array([41, 0, 1, 2])).all()


@pytest.mark.parametrize("finite", [4, 2], ids=["exactly_k", "fewer_than_k"])
def test_a_row_with_few_finite_scores(finite, form):
    """``k`` finite scores are all chosen, in order; with fewer the minus
    infinities follow in index order, as the sorted form lists them."""
    k, e = 4, 16
    scores = jnp.full((128, e), -jnp.inf)
    at = (np.arange(128)[:, None] * 3 + np.arange(finite) * 5) % e
    scores = scores.at[np.arange(128)[:, None], at].set(
        rand((128, finite), 0))
    idx, chosen = both_forms(form, scores, k, rand((128, e), 1))
    assert (np.sort(idx[:, :finite], axis=-1) == np.sort(at, axis=-1)).all()
    assert (np.diff(idx[:, finite:], axis=-1) > 0).all()
    assert np.isfinite(chosen).all()        # the values', not the scores'


def test_leading_axes_are_kept(form):
    scores = rand((2, 128, 24), 0)
    idx, chosen = both_forms(form, scores, 3)
    assert idx.shape == chosen.shape == (2, 128, 3)


RULE = [
    # backend, tokens, experts, k, groups -> form
    ("tpu", 8192, 512, 8, 8, "kernel"),        # ling3flash
    ("tpu", 8192, 256, 8, 1, "kernel"),        # joyai
    ("tpu", 32768, 32, 4, 1, "kernel"),        # lfm2moe
    ("tpu", 16384, 64, 6, 1, "kernel"),        # smallthinker
    ("cpu", 8192, 512, 8, 8, "sorted"),        # no TPU
    ("gpu", 8192, 256, 8, 1, "sorted"),
    ("tpu", 16, 256, 8, 1, "sorted"),          # a model's init pass
    ("tpu", 8200, 256, 8, 1, "sorted"),        # not whole 128-token slabs
    ("tpu", 8192, 36, 4, 1, "sorted"),         # experts not whole sublanes
    ("tpu", 8192, 96, 4, 24, "sorted"),        # groups of 4: nor these
    ("tpu", 8192, 2048, 8, 1, "sorted"),       # more columns than a slab
    ("tpu", 8192, 8, 16, 1, "sorted"),         # more asked than there are
]


@pytest.mark.parametrize("backend,t,e,k,n_group,want", RULE)
def test_the_form_follows_from_the_backend_and_the_shape(
        backend, t, e, k, n_group, want, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert moe._select_form(t, e, k, n_group) == want


def test_no_tpu_here_so_the_routers_sort():
    assert jax.default_backend() == "cpu"
    for family, (e, k, n_group, *_) in ROUTERS.items():
        assert moe._select_form(TOKENS, e, k, n_group) == "sorted"
    text = jax.jit(lambda x, w: route("gqa_moe", x, w, None)).lower(
        rand((TOKENS, WIDTH), 0), rand((WIDTH, 64), 1)).as_text()
    assert "top_k" in text and "bf_moe_select" not in text


@pytest.mark.parametrize("family", sorted(ROUTERS))
@pytest.mark.parametrize("name", ["sorted", "kernel_interpret"])
def test_the_counter_counts_the_kernel_s_rows_and_not_the_fallback_s(
        name, family, form):
    from bluefog_tpu.metrics import registry

    e, k, n_group, topk_group = ROUTERS[family][:4]
    x, w = rand((TOKENS, WIDTH), 0, jnp.bfloat16), rand((WIDTH, e), 1)
    form(name)
    registry.metrics_stop()
    registry._STOPPED = False
    reg = registry.metrics_start()
    try:
        for _ in range(2):
            jax.block_until_ready(jax.jit(lambda x, w: route(
                family, x, w, jnp.zeros((e,))))(x, w))
        jax.effects_barrier()
        snap = reg.snapshot()
    finally:
        registry.metrics_stop()
        registry._STOPPED = False
    ranked = snap.get("bf_moe_select_kernel_rows_total", 0)
    assert ranked == (2 * TOKENS if name == "kernel_interpret" else 0)
    kept = snap.get("bf_moe_groups_kept_total", 0)
    assert kept == (2 * TOKENS * topk_group if n_group > 1 else 0)


def test_with_metrics_off_the_counter_adds_nothing_to_the_program(form):
    form("sorted")
    x, w = rand((TOKENS, WIDTH), 0, jnp.bfloat16), rand((WIDTH, 64), 1)
    text = jax.jit(lambda x, w: route("gqa_moe", x, w, None)).lower(
        x, w).as_text()
    assert "callback" not in text


def test_the_sorted_form_is_the_parent_s_program():
    """The fallback is ``lax.top_k`` and ``take_along_axis`` as the routers
    held them before the kernel: the same jaxpr, equation for equation."""
    x, w, bias = rand((TOKENS, WIDTH), 0), rand((WIDTH, 64), 1), rand((64,), 2)

    def parent(x, w, bias):
        s = jax.nn.sigmoid(jnp.dot(x, w, precision=lax.Precision.HIGHEST))
        steer = s + lax.stop_gradient(bias)
        grouped = steer.reshape(TOKENS, 4, 16)
        _, kept = lax.top_k(lax.top_k(grouped, 2)[0].sum(-1), 2)
        open_groups = jnp.any(kept[..., None] == jnp.arange(4), axis=1)
        steer = jnp.where(open_groups[..., None], grouped,
                          -jnp.inf).reshape(TOKENS, 64)
        _, idx = lax.top_k(steer, 4)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        return idx, 2.5 * chosen / jnp.sum(chosen, axis=-1, keepdims=True)

    def ours(x, w, bias):
        return moe.sigmoid_topk_router(x, w, bias, top_k=4, scale=2.5,
                                       n_group=4, topk_group=2)

    def primitives(f):
        return [str(e.primitive) for e in jax.make_jaxpr(f)(
            x, w, bias).jaxpr.eqns]
    assert primitives(ours) == primitives(parent)


def test_the_benchmark_script_runs_the_three_forms_at_a_tiny_shape(tmp_path):
    """``benchmarks/router_select_bench.py``, which times one router call in
    the sorted form, as unrolled XLA rounds and through the kernel on the
    chip (PERF.md section 6, PR 45), at its tiny shape: the three forms run
    (the kernel in the interpreter) and agree to the bit, forward and with
    the gradient, the program's own rule is back in place afterwards, and a
    CPU run names itself and gives no device time."""
    import json
    import os
    import sys

    from tests._util import REPO
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import router_select_bench

    own = moe._top_k, moe._select_form
    out = router_select_bench.main(
        ["--shapes", "tiny", "--out", str(tmp_path / "bench.json")])
    assert (moe._top_k, moe._select_form) == own
    assert out["platform"] == "cpu"
    for pass_ in ("fwd", "grad"):
        for name in router_select_bench.FORMS:
            entry = out[f"tiny.{pass_}.{name}"]
            assert entry["apart_from_sorted"] == []
            assert len(entry["wall_ms"]) == 3 and entry["device_ms"] is None
            assert entry["lower_s"] > 0 and entry["compile_s"] > 0
    with open(tmp_path / "bench.json") as f:
        assert json.load(f) == json.loads(json.dumps(out))
