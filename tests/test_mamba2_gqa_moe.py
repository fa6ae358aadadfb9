"""Mamba-2 mixers beside un-positioned grouped-query attention, blocks that
are a mixer or a feed-forward alone, and the expert layer Nemotron-H states
(a sigmoid top-k router with a selection bias over **ungated** relu-squared
experts of a width that is not whole lanes, one shared expert of a width of
its own), against the plain reference
``chipbench/mamba2_gqa_moe_reference.py``: tiny widths, f32, seeded random
weights, on the CPU."""

import importlib.util
import json
import os
import re
import sys

import flax.linen
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bluefog_tpu.models.transformer import (  # noqa: E402
    Block, ExpertSizes, GPTConfig, GroupedSizes, HybridSizes, KdaSizes,
    LatentSizes, Mamba2Mixer, Mamba2Sizes, RoutedFFN, TransformerLM,
    next_token_loss)
from bluefog_tpu.ops import local_attention  # noqa: E402
from bluefog_tpu.ops import moe as moe_ops  # noqa: E402
from bluefog_tpu.ops.moe import (  # noqa: E402
    ACTIVATIONS, routed_experts, sigmoid_topk_router)
from chipbench import mamba2_gqa_moe_reference as ref  # noqa: E402

DATA = os.path.join(REPO, "tests", "data", "mamba2_gqa_moe")
M, A, E_ = "mamba2", "full_attention", "feed_forward"
LETTERS = "MEM*E"        # every kind of block; the cell holds MEMEM*EME
KINDS = tuple({"M": M, "*": A, "E": E_}[c] for c in LETTERS)
E, K, VOCAB, EPS = 16, 3, 96, 1e-20
SIZES = {"kinds": LETTERS, "head_dim": 16, "eps": 1e-5, "mamba_heads": 4,
         "mamba_groups": 2, "mamba_state": 16, "top_k": K, "scale": 2.5,
         "weight_eps": EPS, "held_first": 4, "train_router": True}


def experts(**over):
    return ExpertSizes(**{**dict(
        num_experts=E, top_k=K, width=24, num_shared=1, scale=2.5,
        held=(4, 4), first_dense=0, activation="relu2", gated=False,
        shared_width=40, weight_eps=EPS), **over})


def config(**over):
    return GPTConfig(**{**dict(
        vocab_size=VOCAB, hidden_size=64, num_layers=len(KINDS), num_heads=4,
        dtype=jnp.float32, attention="grouped_query", ffn="routed+shared",
        norm="rmsnorm", position="none", norm_eps=1e-5, layer_types=KINDS,
        grouped=GroupedSizes(kv_heads=2, head_dim=16, window=64,
                             rope_theta=1e4),
        mamba2=Mamba2Sizes(heads=4, head_dim=8, state=16, groups=2, conv=4),
        experts=experts()), **over})


def shaken(params, seed=5, scale=0.05):
    """Every leaf moved off its initial value, so that the unit scales and
    the skip carry a gradient worth comparing."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten([
        leaf + scale * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


def rand(shape, seed, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape)


def assert_trees_close(got, want, tol=5e-5):
    """Leaf by leaf, relative to the reference leaf's largest magnitude."""
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < tol, (jax.tree_util.keystr(path), err)


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 21), 0, VOCAB)


@pytest.fixture(scope="module")
def variables(tokens):
    """Shaken parameters and a selection bias that is not zero."""
    model = TransformerLM(config())
    made = jax.jit(model.init)(jax.random.PRNGKey(0), tokens[:, :-1])
    state = {"buffers": jax.tree_util.tree_map(
        lambda b: 0.3 * rand(b.shape, 9), made["buffers"])}
    return shaken(made["params"]), state


def dense_attention(q, k, v, **mask):
    return local_attention(q, k, v, causal=True, backend="dense", **mask)


# ---- the Mamba-2 mixer -------------------------------------------------------

def test_the_mixer_matches_the_reference_in_value_and_gradient():
    module = Mamba2Mixer(config())
    u = rand((2, 37, 64), 3)
    p = shaken(module.init(jax.random.PRNGKey(0), u)["params"], scale=0.2)
    assert jax.tree_util.tree_map(jnp.shape, p) == {
        "in_proj": {"kernel": (64, 2 * 32 + 2 * 2 * 16 + 4)},
        "out_proj": {"kernel": (32, 64)}, "conv_kernel": (4, 96),
        "conv_bias": (96,), "A_log": (4,), "D": (4,), "dt_bias": (4,),
        "norm_scale": (32,)}
    probe = rand((2, 37, 64), 4)

    def value(fn):
        return jax.jit(jax.value_and_grad(
            lambda p, u: jnp.sum(probe * fn(p, u)), argnums=(0, 1)))(p, u)

    got, got_grads = value(lambda p, u: module.apply({"params": p}, u))
    want, want_grads = value(lambda p, u: ref.mamba2(p, u, SIZES))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert_trees_close(got_grads, want_grads)


def test_the_mixer_s_initialisers_are_mamba_2_s():
    p = Mamba2Mixer(config(mamba2=Mamba2Sizes(
        heads=64, head_dim=2, state=4, groups=8, conv=4))).init(
            jax.random.PRNGKey(2), jnp.zeros((1, 8, 64)))["params"]
    assert float(jnp.min(p["A_log"])) >= 0.0                  # log 1
    assert float(jnp.max(p["A_log"])) <= float(np.log(16.0)) + 1e-6
    np.testing.assert_array_equal(p["D"], 1.0)
    np.testing.assert_array_equal(p["norm_scale"], 1.0)
    step = jax.nn.softplus(p["dt_bias"])      # log-uniform in [1e-3, 1e-1]
    assert 1e-3 * 0.999 <= float(jnp.min(step))
    assert float(jnp.max(step)) <= 1e-1 * 1.001
    assert float(jnp.max(jnp.abs(p["conv_kernel"]))) <= 0.5
    assert float(jnp.max(jnp.abs(p["conv_bias"]))) <= 0.5


@pytest.mark.parametrize("t", [0, 4, 20])
def test_the_mixer_is_causal(t):
    module = Mamba2Mixer(config())
    u = rand((1, 24, 64), 3)
    p = shaken(module.init(jax.random.PRNGKey(0), u)["params"], scale=0.2)
    base = module.apply({"params": p}, u)
    out = module.apply({"params": p}, u.at[:, t].add(1.0))
    np.testing.assert_array_equal(out[:, :t], base[:, :t])
    assert float(jnp.max(jnp.abs(out[:, t:] - base[:, t:]))) > 1e-3


# ---- the convolution's kernels, and the form before them ------------------------

def test_the_mixer_on_the_convolution_s_kernels_equals_the_plain_form(
        monkeypatch):
    """Widths that tile (128 inner channels, one group of 128 states): the
    convolution, bias and SiLU in ``bf_cconv_fwd`` / ``bf_cconv_bwd``
    (interpreted), a call a piece of the scan's operands, against the
    ``jax.numpy`` form: output, input gradient and every leaf's."""
    from bluefog_tpu.models import transformer
    from bluefog_tpu.ops import short_conv

    module = Mamba2Mixer(config(mamba2=Mamba2Sizes(
        heads=4, head_dim=32, state=128, groups=1, conv=4)))
    u = rand((2, 48, 64), 3)
    p = shaken(module.init(jax.random.PRNGKey(0), u)["params"], scale=0.2)
    assert p["conv_kernel"].shape == (4, 384)
    probe = rand((2, 48, 64), 4)

    def value():
        return jax.jit(jax.value_and_grad(lambda p, u: jnp.sum(
            probe * module.apply({"params": p}, u)), argnums=(0, 1)))(p, u)

    want, want_grads = value()
    calls = []

    def interpreted(x, *args, **kwargs):
        calls.append((x.shape, kwargs))
        return short_conv.silu_short_conv(x, *args, **kwargs,
                                          backend="pallas_interpret")

    monkeypatch.setattr(transformer, "silu_short_conv", interpreted)
    got, got_grads = value()
    assert calls == [((2, 48, 2 * 128 + 2 * 128 + 4),
                      {"offset": 128, "pieces": (128, 128, 128)})]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert_trees_close(got_grads, want_grads, tol=1e-5)


def test_the_fence_is_on_the_cotangent_alone():
    """Forward the in projection's output itself (no instruction); backward
    one barrier on the sum of the pieces its readers hand back."""
    from bluefog_tpu.models.transformer import _sum_cotangents_once

    x = rand((3, 8), 1)

    def readers(fence):
        def total(x):
            y = fence(x)
            return jnp.sum(y[:, :2] ** 2) + jnp.sum(jnp.sin(y[:, 2:]))
        return total

    fenced, plain = readers(_sum_cotangents_once), readers(lambda x: x)
    assert "optimization_barrier" not in str(jax.make_jaxpr(fenced)(x))
    assert str(jax.make_jaxpr(jax.grad(fenced))(x)).count(
        "optimization_barrier") == 1
    np.testing.assert_array_equal(fenced(x), plain(x))
    np.testing.assert_array_equal(jax.grad(fenced)(x), jax.grad(plain)(x))


class MixerBeforeTheKernels(Mamba2Mixer):
    """``Mamba2Mixer`` as PR 48 wrote it: the convolution over all of
    ``xBC`` in ``jax.numpy`` on an f32 copy of the projection's slice, the
    scan's operands sliced from its result."""

    @flax.linen.compact
    def __call__(self, y):
        from bluefog_tpu.models import transformer as tr

        cfg, sizes = self.cfg, self.cfg.mamba2
        h, p, n, g = sizes.heads, sizes.head_dim, sizes.state, sizes.groups
        inner, lead = h * p, y.shape[:-1]
        nn, f32 = flax.linen, jnp.float32
        dense = lambda width, name: nn.Dense(
            width, use_bias=False, dtype=cfg.dtype, name=name)
        within = tr._uniform_within(sizes.conv ** -0.5)
        zxbcdt = dense(2 * inner + 2 * g * n + h, "in_proj")(y)
        z = zxbcdt[..., :inner]
        delta = nn.softplus(zxbcdt[..., -h:].astype(f32) + self.param(
            "dt_bias", tr._step_bias_init(floor=1e-4), (h,), f32))
        a = -jnp.exp(self.param("A_log", tr._a_log_init, (h,), f32))
        skip = self.param("D", nn.initializers.ones, (h,), f32)
        taps = self.param("conv_kernel", within,
                          (sizes.conv, inner + 2 * g * n), f32)
        bias = self.param("conv_bias", within, (inner + 2 * g * n,), f32)
        xbc = nn.silu(tr.causal_depthwise_conv(
            zxbcdt[..., inner:-h].astype(f32), taps, bias)).astype(cfg.dtype)
        o = tr.ssd(xbc[..., :inner].reshape(lead + (h, p)), delta, a,
                   xbc[..., inner:inner + g * n].reshape(lead + (g, n)),
                   xbc[..., inner + g * n:].reshape(lead + (g, n)), skip)
        gated = o.reshape(lead + (inner,)).astype(f32) * nn.silu(
            z.astype(f32))
        scale = self.param("norm_scale", nn.initializers.ones, (inner,), f32)
        grouped = gated.reshape(lead + (g, inner // g))
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, axis=-1, keepdims=True)
            + cfg.norm_eps)
        o = (grouped.reshape(lead + (inner,)) * scale).astype(cfg.dtype)
        return dense(cfg.hidden_size, "out_proj")(o)


@pytest.mark.parametrize("remat", [False, True], ids=["stored", "remat"])
def test_on_a_cpu_the_model_computes_what_it_did_bit_for_bit(
        remat, tokens, monkeypatch):
    """``'auto'`` off a TPU is the ``jax.numpy`` form over all of ``xBC`` at
    once: the loss and every leaf's gradient of a model of two Mamba-2
    blocks equal those of the mixer written as it was before
    ``silu_short_conv``, to the bit."""
    from bluefog_tpu.models import transformer

    cfg = config(num_layers=2, layer_types=(M, M), ffn="gelu", experts=None,
                 remat=remat)
    params = shaken(jax.jit(TransformerLM(cfg).init)(
        jax.random.PRNGKey(0), tokens[:, :-1])["params"])

    def loss_and_grads():
        model = TransformerLM(cfg)
        return jax.jit(jax.value_and_grad(lambda p: next_token_loss(
            model, p, {}, tokens)))(params)

    got, got_grads = loss_and_grads()
    monkeypatch.setattr(transformer, "Mamba2Mixer", MixerBeforeTheKernels)
    want, want_grads = loss_and_grads()
    assert float(got) == float(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))
    assert float(jnp.max(jnp.abs(
        got_grads["block_0"]["mixer"]["conv_bias"]))) > 0.0


# ---- the ungated expert layer ---------------------------------------------------

def reference_layer(p, bias, y, sizes, first):
    weights = ref.route(p["router"], bias, y, sizes)
    return ref.expert_layer(p, y, weights, first)


@pytest.mark.parametrize("backend", ["ragged", "gmm_interpret"])
def test_ungated_experts_192_wide_match_the_reference(backend):
    """A width that is not whole lanes (192 = 128 + 64), a selection bias
    that is not zero, through the grouped-matmul kernels in the interpreter
    and through ``lax.ragged_dot``: value and every gradient."""
    d, f, count, first, t = 128, 192, 4, 4, 64
    x, router = rand((t, d), 1), rand((d, E), 2, 0.3)
    bias = rand((E,), 3, 0.3)
    p = {"router": router, "w_up": rand((count, d, f), 4, d ** -0.5),
         "w_down": rand((count, f, d), 5, f ** -0.5),
         "shared": {"up": {"kernel": jnp.zeros((d, 8))},
                    "down": {"kernel": jnp.zeros((8, d))}}}
    sizes = {**SIZES, "held_first": first}
    probe = rand((t, d), 6)

    def system(x, w_up, w_down):
        idx, weights = sigmoid_topk_router(x, router, bias, top_k=K,
                                           scale=2.5, eps=EPS)
        y, record = routed_experts(
            x, idx, weights, None, w_up, w_down, num_experts=E,
            held=(first, count), backend=backend, activation="relu2")
        return jnp.sum(probe * y), (y, record)

    def plain(x, w_up, w_down):
        y = reference_layer({**p, "w_up": w_up, "w_down": w_down}, bias, x,
                            sizes, first)
        return jnp.sum(probe * y), y

    (_, (got, record)), got_grads = jax.value_and_grad(
        system, argnums=(0, 1, 2), has_aux=True)(x, p["w_up"], p["w_down"])
    (_, want), want_grads = jax.value_and_grad(
        plain, argnums=(0, 1, 2), has_aux=True)(x, p["w_up"], p["w_down"])
    assert int(record["row_passes"]) >= 1
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert got_grads[1].shape == (count, d, f)        # the leaves' own shapes
    assert got_grads[2].shape == (count, f, d)
    assert_trees_close(got_grads, want_grads)


def test_an_ungated_layer_builds_two_leaves_and_no_gate():
    layer = RoutedFFN(config())
    p = layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))["params"]
    assert jax.tree_util.tree_map(jnp.shape, p) == {
        "router": (64, E), "w_up": (4, 64, 24), "w_down": (4, 24, 64),
        "shared": {"up": {"kernel": (64, 40)}, "down": {"kernel": (40, 64)}}}
    gated = RoutedFFN(config(experts=experts(gated=True, shared_width=None)))
    p = gated.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))["params"]
    assert set(p) == {"router", "w_gate", "w_up", "w_down", "shared"}
    assert set(p["shared"]) == {"gate", "up", "down"}
    assert p["shared"]["up"]["kernel"].shape == (64, 24)      # 1 x width
    np.testing.assert_array_equal(
        ACTIVATIONS["relu2"](jnp.array([-2.0, 0.0, 3.0])), [0.0, 0.0, 9.0])


def test_the_tiles_of_a_padded_width_are_whole(monkeypatch):
    """1,856 columns reach the grouped products as 1,920 = 3 x 640; left
    ragged, the tiler could give them 128 columns a step and no more."""
    assert moe_ops._gmm_tiling(6144, 2688, 1920) == (256, 896, 640)
    assert moe_ops._gmm_tiling(6144, 1920, 2688) == (256, 640, 896)
    assert moe_ops._gmm_tiling(6144, 2688, 1856) == (256, 896, 128)
    x = jnp.ones((8, 16), jnp.bfloat16)
    leaves = (jnp.ones((2, 16, 1856)), jnp.ones((2, 1856, 16)))
    up, down = moe_ops._cast_experts(x, leaves, "ragged")    # as they are
    assert up.shape == (2, 16, 1856) and down.shape == (2, 1856, 16)
    up, down = moe_ops._cast_experts(x, leaves, "gmm")
    assert up.shape == (2, 16, 1920) and down.shape == (2, 1920, 16)
    assert up.dtype == down.dtype == jnp.bfloat16
    assert not up[..., 1856:].any() and not down[:, 1856:].any()
    same = moe_ops._cast_experts(x, (jnp.ones((2, 16, 768)),) * 2 + (
        jnp.ones((2, 768, 16)),), "gmm")
    assert [w.shape for w in same] == [(2, 16, 768)] * 2 + [(2, 768, 16)]


def test_sixteen_shares_of_an_expert_block_add_up_to_the_uncut_block():
    """Sixteen chips hold one expert each of sixteen; the router, the norm
    and the shared expert are every chip's alike.  Their blocks' results,
    the shared expert counted once, sum to the uncut reference block's."""
    x = rand((2, 12, 64), 1)
    whole = RoutedFFN(config(experts=experts(held=(0, E))))
    p = shaken(whole.init(jax.random.PRNGKey(0), x)["params"], scale=0.1)
    bias = 0.3 * rand((E,), 7)
    state = {"buffers": {"selection_bias": bias}}
    shared = ref.relu2_mlp(x, p["shared"]["up"]["kernel"],
                           p["shared"]["down"]["kernel"])
    total = jnp.zeros_like(x)
    for i in range(E):
        share = RoutedFFN(config(experts=experts(held=(i, 1))))
        p_i = {**p, "w_up": p["w_up"][i:i + 1],
               "w_down": p["w_down"][i:i + 1]}
        total = total + share.apply({"params": p_i, **state}, x) - shared
    want = reference_layer(p, bias, x, SIZES, 0)
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    np.testing.assert_allclose(
        whole.apply({"params": p, **state}, x), want, atol=2e-5)
    assert float(jnp.max(jnp.abs(shared))) > 1e-2


# ---- the whole model ----------------------------------------------------------

@pytest.mark.parametrize("train_router", [True, False],
                         ids=["router_trains", "router_constant"])
@pytest.mark.parametrize("remat", [False, True], ids=["stored", "remat"])
def test_model_loss_logits_and_gradients_match_the_reference(
        remat, train_router, variables, tokens):
    cfg = config(remat=remat, experts=experts(train_router=train_router))
    model = TransformerLM(cfg)
    params, state = variables
    sizes = {**SIZES, "train_router": train_router}
    logits = jax.jit(lambda p: model.apply(
        {"params": p, **state}, tokens[:, :-1], attn_fn=dense_attention))(
            params)
    np.testing.assert_allclose(
        logits, jax.jit(lambda p: ref.logits(
            sizes, p, state, tokens[:, :-1]))(params), atol=5e-5)
    got, got_grads = jax.jit(jax.value_and_grad(
        lambda p: next_token_loss(model, p, state, tokens,
                                  attn_fn=dense_attention)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(sizes, p, state, tokens)))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert_trees_close(got_grads, want_grads, tol=1e-4)
    router = got_grads["block_1"]["moe"]["router"]
    assert bool(jnp.any(router != 0)) == train_router


CONTROLS = ("no_skip", "norm_all_channels", "norm_before_gate",
            "relu_for_relu2", "no_scale", "rotary_attention", "wrong_group")


@pytest.mark.parametrize("control", CONTROLS)
def test_the_reference_tells_each_wrong_model_apart(control, variables,
                                                    tokens):
    """The controls the chip's limits are held against
    (``benchmarks/mamba2_gqa_moe_controls.py``), at the tiny size: each
    changes the plain model in one place and moves its loss."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import mamba2_gqa_moe_controls as controls

    params, state = variables

    def loss():       # traced anew: the control is in force while it is
        return float(jax.jit(lambda p: ref.loss(SIZES, p, state, tokens))(
            params))

    sound = loss()
    undo = controls.altered(control)
    try:
        wrong = loss()
    finally:
        undo()
    assert abs(wrong - sound) / sound > 2e-4, (control, sound, wrong)
    assert loss() == sound


@pytest.mark.parametrize("control", ["decay_bf16", "state_bf16"])
def test_the_controls_reach_into_the_system_s_scan(control, variables):
    """Over 141 tokens: the state crosses a chunk's boundary."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import mamba2_gqa_moe_controls as controls

    params, state = variables
    model = TransformerLM(config())
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 142), 0, VOCAB)

    def loss():
        return float(next_token_loss(model, params, state, tokens,
                                     attn_fn=dense_attention))

    sound = loss()
    undo = controls.altered(control)
    try:
        wrong = loss()
    finally:
        undo()
    assert wrong != sound and abs(wrong - sound) / sound < 0.05
    assert loss() == sound


# ---- scopes and counters --------------------------------------------------------

SCOPES = ("bf.ssd.project", "bf.ssd.conv", "bf.ssd.scan", "bf.ssd.norm_gate",
          "bf.moe.route", "bf.moe.dispatch", "bf.moe.experts",
          "bf.moe.combine", "bf.mlp.dense", "bf.attn.project")


@pytest.mark.parametrize("remat", [False, True], ids=["stored", "remat"])
def test_scopes_the_benchmark_reads_reach_the_compiled_step_unnested(
        remat, variables, tokens):
    """Every scope of the new layers reaches the compiled text, in the
    forward, the backward and (under remat) the recomputed pass; no op sits
    under two; every heavy op carries one; and ``phases/step_ssd.json``
    sends each to the phase its metric reads."""
    params, state = variables
    model = TransformerLM(config(remat=remat, experts=experts(
        train_router=False)))
    text = jax.jit(jax.grad(lambda p: next_token_loss(
        model, p, state, tokens))).lower(params).compile().as_text()
    by_pass = {"forward": set(), "backward": set(), "recompute": set()}
    heavy = re.compile(r" (dot|convolution|gather|scatter|reduce|custom-call)\(")
    for line in text.splitlines():
        named = re.search(r'op_name="([^"]*)"', line)
        if named is None:
            continue
        for one_op in named.group(1).split(";"):
            found = set(re.findall(r"bf\.[a-z]+\.[a-z_]+", one_op))
            assert len(found) <= 1, one_op      # leaf-level, never nested
            which = ("recompute" if "rematted_computation" in one_op else
                     "backward" if "transpose(" in one_op else "forward")
            by_pass[which] |= found
        if heavy.search(line):
            assert re.search(r"bf\.[a-z]+\.[a-z_]+", named.group(1)), line[:300]
    seen = by_pass["forward"] | by_pass["backward"] | by_pass["recompute"]
    assert set(SCOPES) <= seen, set(SCOPES) - seen
    for scope in SCOPES[:4]:
        assert scope in by_pass["backward"], scope
        assert (scope in by_pass["recompute"]) == remat, scope
    rules = json.load(open(os.path.join(
        REPO, "chipbench", "phases", "step_ssd.json")))["rules"]

    def phase_of(scope):
        return next(phase for phase, field, pattern in rules
                    if field == "op_name" and re.search(pattern, scope))

    assert phase_of("bf.ssd.scan") == "ssd_scan"
    for scope in ("bf.ssd.project", "bf.ssd.conv", "bf.ssd.norm_gate"):
        assert phase_of(scope) == "ssd_mix"
    for scope in ("bf.moe.route", "bf.moe.dispatch", "bf.moe.combine"):
        assert phase_of(scope) == "expert_dispatch"
    assert phase_of("bf.moe.experts") == "expert_ffn"


def test_counters_of_the_new_layers(variables, tokens):
    from bluefog_tpu.metrics import registry

    params, state = variables
    registry.metrics_stop()
    registry._STOPPED = False
    reg = registry.metrics_start()
    try:
        model = TransformerLM(config())
        jax.jit(lambda p: model.apply({"params": p, **state},
                                      tokens[:, :-1]))(params)
        jax.effects_barrier()
        snap = reg.snapshot()
        assert snap["bf_ssd_calls_total"] == LETTERS.count("M")
        assert snap["bf_cconv_calls_total"] == LETTERS.count("M")
        assert snap["bf_attn_full_calls_total"] == LETTERS.count("*")
        assigned = LETTERS.count("E") * 2 * 20 * K
        assert snap["bf_moe_assignments_total"] == assigned
        assert 0 < snap["bf_moe_assignments_held_total"] < assigned
    finally:
        registry.metrics_stop()
        registry._STOPPED = False


# ---- one Block, one sub-layer ---------------------------------------------------

def test_a_block_is_one_norm_and_one_sub_layer(variables):
    params, state = variables
    for i, kind in enumerate(KINDS):
        block = params[f"block_{i}"]
        assert set(block) == {M: {"ln1", "mixer"}, A: {"ln1", "attn"},
                              E_: {"ln2", "moe"}}[kind], (i, kind)
    assert set(state["buffers"]) == {
        f"block_{i}" for i, kind in enumerate(KINDS) if kind == E_}
    assert "lm_head" in params and config().single_sublayer
    x = rand((1, 8, 64), 2)
    for kind, leaf in ((M, "mixer"), (A, "attn"), (E_, "moe")):
        made = Block(config(), mixer=kind).init(
            jax.random.PRNGKey(0), x, dense_attention,
            jnp.arange(8)[None])["params"]
        assert len(made) == 2 and leaf in made


def test_the_model_has_no_positional_encoding(variables, tokens):
    """Neither a table nor a turn: the attention block alone sees a set, and
    the Mamba-2 blocks order the tokens."""
    params, state = variables
    assert "pos" not in params
    cfg = config(num_layers=2, layer_types=(A, E_), mamba2=None)
    model = TransformerLM(cfg)
    made = model.init(jax.random.PRNGKey(0), tokens[:, :-1])
    logits = model.apply(made, tokens[:, :-1], attn_fn=dense_attention)
    swapped = tokens[:, :-1].at[:, [0, 1]].set(tokens[:, [1, 0]])
    moved = model.apply(made, swapped, attn_fn=dense_attention)
    # the last position attends over the same set of earlier tokens
    np.testing.assert_allclose(moved[:, -1], logits[:, -1], atol=1e-5)
    ordered = TransformerLM(config(num_layers=2, layer_types=(M, E_)))
    made = ordered.init(jax.random.PRNGKey(0), tokens[:, :-1])
    assert float(jnp.max(jnp.abs(
        ordered.apply(made, swapped)[:, -1]
        - ordered.apply(made, tokens[:, :-1])[:, -1]))) > 1e-4


@pytest.mark.parametrize("over,message", [
    (dict(mamba2=None), "mamba2"),
    (dict(layer_types=(A,) * len(KINDS)), "mamba2"),
    (dict(mamba2=Mamba2Sizes(heads=4, head_dim=8, state=16, groups=3)),
     "do not divide"),
    (dict(experts=experts(first_dense=1)), "feed_forward"),
    (dict(experts=experts(router_input="block")), "feed_forward"),
    (dict(experts=experts(num_shared=0)), "shared_width"),
    (dict(experts=experts(activation="gelu")), "activation"),
    (dict(heads_held=(0, 2)), "heads_held"),
    (dict(layer_types=KINDS[:-1] + ("feed_forwards",)),
     "unknown layer type"),
])
def test_config_refuses_what_means_nothing(over, message):
    with pytest.raises(ValueError, match=message):
        config(**over)


def test_feed_forward_blocks_belong_to_the_grouped_query_layers():
    """Among the SambaY mixers or the linear/latent layers a block of the
    feed-forward alone is refused: the families are not mixed."""
    with pytest.raises(ValueError, match="mixes layer families"):
        GPTConfig(num_layers=2, ffn="swiglu", position="none",
                  layer_types=("mamba", E_), hybrid=HybridSizes())
    with pytest.raises(ValueError, match="mixes layer families"):
        GPTConfig(num_layers=2, attention="latent", position="rotary",
                  layer_types=("kda", E_), kda=KdaSizes(),
                  latent=LatentSizes(q_lora_rank=None))
    # a dense feed-forward alone is a block too
    dense = config(ffn="swiglu", ffn_width=96, experts=None)
    shapes = jax.eval_shape(
        TransformerLM(dense).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    assert set(shapes["block_1"]) == {"ln2", "mlp"}


# ---- what the accepted configurations keep ---------------------------------------

@pytest.fixture(scope="module")
def accepted():
    spec = importlib.util.spec_from_file_location(
        "accepted_layers", os.path.join(DATA, "accepted_layers.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def expert_layers_now(accepted):
    return accepted.expert_layers(REPO)


@pytest.fixture(scope="module")
def trees_now(accepted):
    return accepted.trees(REPO)


@pytest.mark.parametrize("name", [
    "gpt2-small", "joyai-llm-flash", "phi-4-mini-flash",
    "smallthinker-21b-a3b", "ling-3.0-flash", "lfm2-8b-a1b"])
def test_an_accepted_configuration_s_tree_is_the_parent_s(name, trees_now):
    """Every leaf's path, shape and dtype at the published widths, as PR
    48's parent commit built them (``accepted_trees.json``)."""
    with open(os.path.join(DATA, "accepted_trees.json")) as f:
        want = json.load(f)[name]
    assert trees_now[name] == want
    widths = {int(m) for path, (shape, _) in want.items()
              if re.search(r"\['w_(gate|up)'\]$", path) for m in shape[-1:]}
    assert all(w % 128 == 0 for w in widths), widths   # no pad in their steps


@pytest.mark.parametrize("family", ["latent_moe", "gqa_moe",
                                    "linear_latent_moe", "conv_gqa_moe"])
def test_an_accepted_expert_layer_is_the_parent_s_bit_for_bit(
        family, expert_layers_now):
    """``joyai``'s, ``smallthinker``'s, ``ling3flash``'s and ``lfm2``'s
    expert layers at their tiny presets: output, the input's gradient and
    every leaf's, equal to the bit to what the parent commit computed."""
    want = np.load(os.path.join(DATA, "accepted_expert_layers.npz"))
    names = [k for k in want.files if k.startswith(family + "/")]
    assert len(names) >= 6
    assert sorted(k for k in expert_layers_now
                  if k.startswith(family + "/")) == sorted(names)
    for name in names:
        np.testing.assert_array_equal(expert_layers_now[name], want[name],
                                      err_msg=name)


# ---- the configuration file --------------------------------------------------------

CELL = "nemotron3nano.t8192.solo"
# the catalog row's `config` (model-configs guide, architectures.jsonl)
CATALOG = {'attention_bias': False,
 'chunk_size': 128,
 'conv_kernel': 4,
 'expand': 2,
 'head_dim': 128,
 'hidden_size': 2688,
 'hybrid_override_pattern': 'MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME',
 'intermediate_size': 1856,
 'layer_norm_epsilon': 1e-05,
 'mamba_head_dim': 64,
 'mamba_hidden_act': 'silu',
 'mamba_num_heads': 64,
 'mamba_proj_bias': False,
 'max_position_embeddings': 262144,
 'mlp_bias': False,
 'mlp_hidden_act': 'relu2',
 'model_type': 'nemotron_h',
 'moe_intermediate_size': 1856,
 'moe_shared_expert_intermediate_size': 3712,
 'n_group': 1,
 'n_groups': 8,
 'n_routed_experts': 128,
 'n_shared_experts': 1,
 'norm_eps': 1e-05,
 'norm_topk_prob': True,
 'num_attention_heads': 32,
 'num_experts_per_tok': 6,
 'num_hidden_layers': 52,
 'num_key_value_heads': 2,
 'num_logits_to_keep': 1,
 'partial_rotary_factor': 1,
 'rescale_prenorm_residual': True,
 'residual_in_fp32': False,
 'rope_theta': 10000,
 'routed_scaling_factor': 2.5,
 'sliding_window': None,
 'ssm_state_size': 128,
 'tie_word_embeddings': False,
 'time_step_floor': 0.0001,
 'time_step_max': 0.1,
 'time_step_min': 0.001,
 'topk_group': 1,
 'use_bias': False,
 'use_conv_bias': True,
 'use_mamba_kernels': True,
 'vocab_size': 131072}
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
           "vocab_size"]


@pytest.fixture(scope="module")
def published():
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    cfg_file, traffic = cells.open_cell(manifest, CELL)
    family = manifest.module("families", cfg_file["family"]).build(
        cfg_file, traffic)
    return manifest, cfg_file, traffic, family


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_every_number_of_the_catalogued_config_is_kept_or_listed(published,
                                                                 key):
    """No width is in ``reduced``; what is there is the chip's share."""
    cfg_file = published[1]
    if key in REDUCED:
        assert cfg_file[key] != CATALOG[key]
        assert cfg_file["deployment"]["published"][key] == CATALOG[key]
    else:
        assert cfg_file[key] == CATALOG[key]
        assert type(cfg_file[key]) is type(CATALOG[key])


def test_the_configuration_file_states_its_cuts_and_its_deployment(published):
    manifest, cfg_file, traffic, family = published
    assert cfg_file["reduced"] == REDUCED
    assert set(cfg_file["changed"]) == set(REDUCED)
    deployment = cfg_file["deployment"]
    pattern = CATALOG["hybrid_override_pattern"]
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (
        23, 23, 6)
    assert cfg_file["hybrid_override_pattern"] == pattern[:9] == "MEMEM*EME"
    assert deployment["chips_sharing_a_layer"] == 16
    assert deployment["router_outputs"] == 128
    assert (deployment["experts_held_first"], deployment["experts_held"],
            cfg_file["n_routed_experts"]) == (0, 8, 8)
    assert deployment["vocabulary_shards"] == 8
    assert cfg_file["vocab_size"] * 8 == 131072
    assert deployment["first_layer"] == 0
    assert deployment["router_trains"] is False
    assert "what_the_cut_distorts" in deployment
    for key in ("positional_encoding", "block", "mamba_inner_width",
                "mamba_norm", "router", "router_precision", "selection_bias",
                "router_gradient", "experts", "expert_width_tiling",
                "initialisers", "optimizer", "compute_dtype"):
        assert key in cfg_file["assumed"], key
    assert "no positional embedding" in (
        cfg_file["assumed"]["positional_encoding"])
    entry = manifest.entry("configs", "nemotron-3-nano-30b-a3b")
    assert entry["source"] == cfg_file["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
        "blob/main/config.json")
    assert entry["reduced"] == REDUCED
    assert (traffic["seq_len"], traffic["batch"], traffic["remat"]) == (
        8192, 2, True)
    cfg = family.model.cfg
    assert cfg.layer_types == tuple(
        {"M": M, "*": A, "E": E_}[c] for c in "MEMEM*EME")
    assert cfg.single_sublayer and cfg.position == "none"
    assert cfg.grouped == GroupedSizes(kv_heads=2, head_dim=128,
                                       window=262144, rope_theta=1e4)
    assert cfg.mamba2 == Mamba2Sizes(heads=64, head_dim=64, state=128,
                                     groups=8, conv=4)
    assert cfg.experts == ExpertSizes(
        num_experts=128, top_k=6, width=1856, num_shared=1, scale=2.5,
        held=(0, 8), first_dense=0, router="sigmoid_noaux_tc",
        activation="relu2", router_input="ffn", train_router=False,
        weight_eps=1e-20, gated=False, shared_width=3712)
    assert (cfg.hidden_size, cfg.num_heads, cfg.norm_eps) == (2688, 32, 1e-5)
    assert cfg.remat and not cfg.tie_head and cfg.dtype == jnp.bfloat16


def test_the_parameter_count_is_the_files_and_the_issue_s_table(published):
    _, cfg_file, _, family = published
    shapes, state = jax.eval_shape(family.init, jax.random.PRNGKey(0))
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == cfg_file["parameters"] == 666_962_944
    sizes = {name: {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
                    for k, v in shapes[name].items()}
             for name in ("block_0", "block_1", "block_5")}
    mixer = (2688 * 10304 + 4096 * 2688 + 5 * 6144 + 3 * 64 + 4096)
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256              # 23,396,352
    moe = 2688 * 128 + 8 * 2 * 2688 * 1856 + 2 * 2688 * 3712  # 100,122,624
    assert sizes["block_0"] == {"mixer": mixer, "ln1": 2688}
    assert mixer + 2688 == 38_744_896
    assert sizes["block_1"] == {"moe": moe, "ln2": 2688}
    assert sizes["block_5"] == {"attn": attention, "ln1": 2688}
    assert shapes["block_1"]["moe"]["w_up"].shape == (8, 2688, 1856)
    assert shapes["block_1"]["moe"]["w_down"].shape == (8, 1856, 2688)
    assert "w_gate" not in shapes["block_1"]["moe"]
    assert shapes["tok"]["embedding"].shape == (16_384, 2_688)
    assert shapes["lm_head"]["kernel"].shape == (2_688, 16_384)
    assert set(state["buffers"]) == {"block_1", "block_3", "block_6",
                                     "block_8"}
    # the uncut model by the same parts: the published 31.6B
    whole = (23 * (mixer + 2688) + 6 * (attention + 2688)
             + 23 * (2688 * 128 + 128 * 2 * 2688 * 1856 + 2 * 2688 * 3712
                     + 2688) + 2 * 131072 * 2688 + 2688)
    assert 31.5e9 < whole < 31.7e9


@pytest.mark.parametrize("key,value", [
    ("use_conv_bias", False), ("mlp_hidden_act", "silu"),
    ("norm_topk_prob", False), ("tie_word_embeddings", True),
    ("model_type", "nemotron"), ("n_group", 2), ("chunk_size", 256),
    ("n_shared_experts", 2), ("hybrid_override_pattern", "MEMEM*EM-"),
    ("hybrid_override_pattern", "EMEMEM*EM")])
def test_family_refuses_what_it_does_not_compute(published, key, value):
    manifest, cfg_file, _, _ = published
    build = manifest.module("families", "mamba2_gqa_moe").build
    config_file = {**cfg_file, key: value}
    if value == "MEMEM*EM-":       # a letter the family has no block for
        config_file["deployment"] = {**cfg_file["deployment"], "published": {
            **cfg_file["deployment"]["published"],
            "hybrid_override_pattern": value}}
    with pytest.raises(SystemExit):
        build(config_file, {"seq_len": 64, "batch": 1, "remat": True})
