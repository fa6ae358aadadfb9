"""Gated short convolutions beside rotary, QK-normed grouped-query attention,
and the expert layer LFM2 states (a sigmoid top-k router with a selection
bias and an epsilon in its normaliser, no shared expert, a leading dense
block, a tied head), against the plain reference
``chipbench/conv_gqa_moe_reference.py``: tiny widths, f32, seeded random
weights, on the CPU."""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bluefog_tpu.models.transformer import (  # noqa: E402
    Block, ExpertSizes, GPTConfig, GroupedQueryAttention, GroupedSizes,
    LatentSizes, RoutedFFN, ShortConv, ShortConvSizes, TransformerLM,
    causal_depthwise_conv, next_token_loss)
from bluefog_tpu.ops import local_attention  # noqa: E402
from bluefog_tpu.ops.moe import sigmoid_topk_router  # noqa: E402
from chipbench import conv_gqa_moe_reference as ref  # noqa: E402

CONV, FULL = "short_conv", "full_rotary_attention"
KINDS = (CONV, FULL, CONV, CONV, CONV)
REF_KINDS = tuple("conv" if kind == CONV else "full_attention"
                  for kind in KINDS)
E, K, VOCAB, THETA, EPS = 8, 2, 96, 10000.0, 1e-6
SIZES = {"kinds": REF_KINDS, "head_dim": 16, "rope_theta": THETA,
         "eps": 1e-5, "dense_blocks": 1, "top_k": K, "scale": 1.0,
         "weight_eps": EPS, "held_first": 2, "train_router": True}


def experts(**over):
    return ExpertSizes(**{**dict(
        num_experts=E, top_k=K, width=32, num_shared=0, scale=1.0,
        held=(2, 2), first_dense=1, weight_eps=EPS), **over})


def grouped(**over):
    return GroupedSizes(**{**dict(kv_heads=2, head_dim=16, window=64,
                                  rope_theta=THETA, qk_norm=True), **over})


def config(**over):
    return GPTConfig(**{**dict(
        vocab_size=VOCAB, hidden_size=64, num_layers=5, num_heads=4,
        dtype=jnp.float32, attention="grouped_query", ffn="routed+shared",
        norm="rmsnorm", position="none", norm_eps=1e-5, ffn_width=96,
        tie_head=True, layer_types=KINDS, grouped=grouped(),
        short_conv=ShortConvSizes(taps=3), experts=experts()), **over})


def shaken(params, seed=5, scale=0.05):
    """Every leaf moved off its initial value, so that the unit scales
    carry a gradient worth comparing."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten([
        leaf + scale * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


def rand(shape, seed, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape)


def assert_trees_close(got, want, tol=2e-5):
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


# ---- the short convolution ---------------------------------------------------

def looped_short_conv(p, u):
    """``ShortConv`` one token at a time, as the equations are written."""
    w_in, w_out, taps = (np.asarray(p["in_proj"]["kernel"], np.float64),
                         np.asarray(p["out_proj"]["kernel"], np.float64),
                         np.asarray(p["conv_kernel"], np.float64))
    u = np.asarray(u, np.float64)
    d = u.shape[-1]
    out = np.zeros_like(u)
    for n in range(u.shape[0]):
        s = []
        for t in range(u.shape[1]):
            projected = u[n, t] @ w_in
            b, c, z = projected[:d], projected[d:2 * d], projected[2 * d:]
            s.append(b * z)
            conv = sum(taps[j] * s[t - 2 + j] for j in range(3)
                       if t - 2 + j >= 0)
            out[n, t] = (c * conv) @ w_out
    return out


def test_short_conv_equals_a_loop_over_time_and_the_reference():
    module = ShortConv(config())
    u = rand((2, 9, 64), 3)
    p = shaken(module.init(jax.random.PRNGKey(0), u)["params"], scale=0.2)
    assert jax.tree_util.tree_map(jnp.shape, p) == {
        "in_proj": {"kernel": (64, 192)}, "out_proj": {"kernel": (64, 64)},
        "conv_kernel": (3, 64)}
    got = module.apply({"params": p}, u)
    np.testing.assert_allclose(got, looped_short_conv(p, u), atol=2e-5)
    np.testing.assert_allclose(got, ref.short_conv(p, u), atol=2e-5)


def test_short_conv_matches_the_reference_in_gradient():
    module = ShortConv(config())
    u, probe = rand((2, 12, 64), 7), rand((2, 12, 64), 8)
    p = shaken(module.init(jax.random.PRNGKey(0), u)["params"], scale=0.2)
    g, w = (jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(p, u) for f in (
        lambda p, u: jnp.sum(probe * module.apply({"params": p}, u)),
        lambda p, u: jnp.sum(probe * ref.short_conv(p, u))))
    np.testing.assert_allclose(g[0], w[0], rtol=2e-5)
    assert_trees_close(g[1], w[1])


@pytest.mark.parametrize("t", [0, 1, 5, 11])
def test_short_conv_is_causal_and_reaches_two_tokens_on(t):
    """A change at token ``t`` moves nothing before ``t``, moves ``t``,
    ``t + 1`` and ``t + 2`` (three taps), and nothing after."""
    module = ShortConv(config())
    u = rand((1, 12, 64), 1)
    p = shaken(module.init(jax.random.PRNGKey(0), u)["params"], scale=0.2)
    moved = module.apply({"params": p}, u.at[0, t].add(1.0))
    delta = np.abs(np.asarray(moved - module.apply({"params": p}, u))).max(
        axis=-1)[0]
    reach = list(range(t, min(t + 3, 12)))
    assert np.all(delta[reach] > 1e-4), delta
    np.testing.assert_array_equal(np.delete(delta, reach), 0.0)


def test_the_shared_convolution_keeps_its_callers_results():
    """``causal_depthwise_conv`` serves the Mamba and KDA mixers too: the
    explicit sum it has always been, bias included."""
    x, kernel, bias = rand((2, 7, 5), 0), rand((4, 5), 1), rand((5,), 2)
    want = np.zeros((2, 7, 5))
    for t in range(7):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(kernel[j] * x[:, t - 3 + j])
    np.testing.assert_allclose(causal_depthwise_conv(x, kernel, bias),
                               want + np.asarray(bias), atol=1e-6)


# ---- attention: the per-head norms and the half-split turn -------------------

@pytest.mark.parametrize("kind", [FULL, "window_rotary_attention",
                                  "full_attention"])
def test_attention_layer_matches_the_reference_in_value_and_gradient(kind):
    """The full rotary layer against the plain one; the two older kinds
    with the norms against the plain layer altered to their definition."""
    window = 5
    cfg = config(grouped=grouped(window=window))
    module = GroupedQueryAttention(cfg, kind)
    y, probe = rand((2, 12, 64), 7), rand((2, 12, 64), 8)
    positions = 3 + jnp.arange(12)
    p = shaken(module.init(jax.random.PRNGKey(0), y, dense_attention,
                           positions[None])["params"])
    assert p["q_norm"]["scale"].shape == p["k_norm"]["scale"].shape == (16,)

    def plain(p, y):
        if kind == FULL:
            return ref.gqa(p, y, positions, SIZES)
        b, t, _ = y.shape
        q, k, v = ((y @ p[n]["kernel"]).reshape(b, t, -1, 16) for n in "qkv")
        q = ref.rms(q, p["q_norm"]["scale"], 1e-5)
        k = ref.rms(k, p["k_norm"]["scale"], 1e-5)
        mask = {}
        if kind == "window_rotary_attention":
            q, k = (ref.rotary(x, positions, THETA) for x in (q, k))
            mask = {"window": window}
        k, v = (jnp.repeat(x, 2, axis=2) for x in (k, v))
        return dense_attention(q, k, v, **mask).reshape(b, t, -1) @ (
            p["o"]["kernel"])

    g, w = (jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(p, y) for f in (
        lambda p, y: jnp.sum(probe * module.apply(
            {"params": p}, y, dense_attention, positions[None])),
        lambda p, y: jnp.sum(probe * plain(p, y))))
    np.testing.assert_allclose(g[0], w[0], rtol=2e-5)
    assert_trees_close(g[1], w[1])


def test_without_qk_norm_the_layer_has_no_norm_leaves_and_differs():
    y, positions = rand((1, 10, 64), 1), jnp.arange(10)[None]
    normed = GroupedQueryAttention(config(), FULL)
    bare = GroupedQueryAttention(config(grouped=grouped(qk_norm=False)), FULL)
    p = shaken(normed.init(jax.random.PRNGKey(0), y, dense_attention,
                           positions)["params"])
    projections = {n: p[n] for n in "qkvo"}
    assert set(bare.init(jax.random.PRNGKey(0), y, dense_attention,
                         positions)["params"]) == set("qkvo")
    assert not np.allclose(
        normed.apply({"params": p}, y, dense_attention, positions),
        bare.apply({"params": projections}, y, dense_attention, positions),
        atol=1e-3)


def test_the_full_rotary_layer_sees_every_key_and_its_positions(variables,
                                                                tokens):
    """Attended keys: no window is handed on.  Positions: rotary is
    relative, so a common offset moves nothing (to rounding) and stretched
    positions do."""
    seen = {}

    def attn_fn(q, k, v, **mask):
        seen.update(q=q.shape, k=k.shape, mask=mask)
        return dense_attention(q, k, v, **mask)

    params, state = variables
    model = TransformerLM(config())
    t = tokens.shape[1] - 1
    apply = jax.jit(lambda positions: model.apply(
        {"params": params, **state}, tokens[:, :-1], positions=positions,
        attn_fn=attn_fn))
    base = apply(jnp.arange(t)[None])
    assert seen == {"q": (2, t, 4, 16), "k": (2, t, 2, 16), "mask": {}}
    np.testing.assert_allclose(apply(1000 + jnp.arange(t)[None]), base,
                               atol=2e-3)
    assert float(jnp.max(jnp.abs(apply(2 * jnp.arange(t)[None])
                                 - base))) > 1e-3


def test_a_model_of_short_convolutions_alone_sees_no_position(tokens):
    model = TransformerLM(config(
        num_layers=2, layer_types=(CONV,) * 2, experts=None, ffn="swiglu",
        grouped=grouped(qk_norm=False)))
    t = tokens.shape[1] - 1
    made = jax.jit(model.init)(jax.random.PRNGKey(0), tokens[:, :-1])
    apply = jax.jit(lambda positions: model.apply(
        made, tokens[:, :-1], positions=positions))
    np.testing.assert_array_equal(apply(7 * jnp.arange(t)[None]),
                                  apply(jnp.arange(t)[None]))


# ---- the router ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_router_is_the_reference_s_with_a_bias_and_the_epsilon(seed):
    x, kernel = rand((50, 32), seed), rand((32, 32), seed + 10)
    bias = 0.4 * rand((32,), seed + 20)
    idx, weights = sigmoid_topk_router(x, kernel, bias, top_k=4, scale=1.0,
                                       eps=EPS)
    s = jax.nn.sigmoid(x @ kernel)
    _, want_idx = jax.lax.top_k(s + bias, 4)
    np.testing.assert_array_equal(idx, want_idx)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(
        weights, chosen / (chosen.sum(-1, keepdims=True) + EPS), rtol=1e-6)
    # the bias steers the selection and is not in the weights
    assert not np.array_equal(idx, jax.lax.top_k(s, 4)[1])
    dense = ref.route(kernel, bias, x, {**SIZES, "top_k": 4})
    np.testing.assert_allclose(
        jnp.take_along_axis(dense, idx, axis=-1), weights, rtol=1e-6)
    assert int((dense > 0).sum()) == 50 * 4
    # an epsilon that shows: the weights no longer sum to one
    _, coarse = sigmoid_topk_router(x, kernel, bias, top_k=4, eps=1e-2)
    assert np.all(np.asarray(coarse.sum(-1)) < 1.0 - 1e-3)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=2e-6)


def old_sigmoid_weights(x, kernel, bias, top_k, scale):
    """The router as it stood before the epsilon (PR 42), ungrouped."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), kernel.astype(
        jnp.float32), precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)


@pytest.mark.parametrize("name,top_k,scale,groups", [
    ("joyai", 8, 2.5, {}), ("ling", 8, 2.5, {"n_group": 8, "topk_group": 4})])
def test_the_epsilon_s_default_leaves_the_older_routing_bit_equal(
        name, top_k, scale, groups):
    """``joyai-llm-flash`` and ``ling-3.0-flash`` state no epsilon: their
    router traces to the program it was (the same equations, none added)
    and gives the same bits; their ``ExpertSizes`` default to 0."""
    x, kernel = rand((64, 48), 3), rand((48, 64), 4)
    bias = jnp.zeros((64,))
    route = lambda **kw: lambda x, k: sigmoid_topk_router(  # noqa: E731
        x, k, bias, top_k=top_k, scale=scale, **groups, **kw)
    got, explicit = (jax.jit(route(**kw))(x, kernel)
                     for kw in ({}, {"eps": 0.0}))
    for a, b in zip(got, explicit):
        np.testing.assert_array_equal(a, b)
    text = lambda f: re.sub(r"\s+", " ", str(jax.make_jaxpr(f)(x, kernel)))  # noqa: E731
    assert text(route()) == text(route(eps=0.0))
    assert text(route()) != text(route(eps=EPS))
    if not groups:
        want = jax.jit(lambda x, k: old_sigmoid_weights(
            x, k, bias, top_k, scale))(x, kernel)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)
        assert text(route()) == text(lambda x, k: old_sigmoid_weights(
            x, k, bias, top_k, scale))
    assert ExpertSizes().weight_eps == 0.0


# ---- the shares add up --------------------------------------------------------

def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference_layer():
    """8 experts, top-2, four chips of 2: ``held = (0, 2) .. (6, 2)``.  No
    shared expert, so nothing is counted twice; the selection bias and the
    epsilon are every chip's alike."""
    whole = RoutedFFN(config(experts=experts(held=(0, E))))
    f = rand((2, 16, 64), 6)
    made = whole.init(jax.random.PRNGKey(0), f)
    params = shaken(made["params"], scale=0.2)
    buffers = {"selection_bias": 0.3 * rand((E,), 4)}
    uncut = whole.apply({"params": params, "buffers": buffers}, f)
    total = jnp.zeros_like(f)
    for first in range(0, E, 2):
        share = {**params, **{name: params[name][first:first + 2]
                              for name in ("w_gate", "w_up", "w_down")}}
        total = total + RoutedFFN(config(experts=experts(
            held=(first, 2)))).apply({"params": share, "buffers": buffers}, f)
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    weights = ref.route(params["router"], buffers["selection_bias"], f,
                        SIZES)
    np.testing.assert_allclose(
        uncut, ref.held_experts(params, f, weights, 0), atol=2e-5)
    assert "shared" not in params


# ---- the whole model -----------------------------------------------------------

@pytest.mark.duration_budget(60)   # the first compile of the plain reference
@pytest.mark.parametrize("train_router", [True, False],
                         ids=["router_trains", "router_held_still"])
@pytest.mark.parametrize("remat", [False, True], ids=["stored", "remat"])
def test_model_loss_logits_and_gradients_match_the_reference(
        remat, train_router, variables, tokens):
    """Five blocks (conv + dense, attention + routed, three conv + routed),
    a selection bias that is not zero, the tied head; with the routing
    weights as constants of the backward pass no router has a gradient, in
    the system and in the reference alike, and the loss is the same."""
    params, state = variables
    model = TransformerLM(config(
        remat=remat, experts=experts(train_router=train_router)))
    sizes = {**SIZES, "train_router": train_router}
    got = jax.jit(jax.value_and_grad(
        lambda p: next_token_loss(model, p, state, tokens)))(params)
    want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(sizes, p, state, tokens)))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)
    assert_trees_close(got[1], want[1])
    assert set(params["block_0"]) == {"ln1", "conv", "ln2", "mlp"}
    assert set(params["block_1"]) == {"ln1", "attn", "ln2", "moe"}
    assert "lm_head" not in params
    for i in range(1, 5):
        router = np.asarray(got[1][f"block_{i}"]["moe"]["router"])
        assert bool(np.any(router)) == train_router, i
    logits = jax.jit(lambda p: model.apply(
        {"params": p, **state}, tokens[:, :-1]))(params)
    np.testing.assert_allclose(
        logits, ref.logits(sizes, params, state, tokens[:, :-1]), atol=2e-5)


CONTROLS = ["qk_norms_dropped", "rotary_left_off", "a_tap_dropped",
            "epsilon_1e-2", "no_selection_bias", "thirds_in_another_order"]


def altered(control, monkeypatch):
    """Change the plain model in one place; returns the ``sizes`` to use."""
    if control == "qk_norms_dropped":
        monkeypatch.setattr(ref, "rms", lambda x, scale, eps: (
            x if scale.shape == (16,) else x * jax.lax.rsqrt(jnp.mean(
                x * x, axis=-1, keepdims=True) + eps) * scale))
    elif control == "rotary_left_off":
        monkeypatch.setattr(ref, "rotary", lambda x, positions, theta: x)
    elif control == "a_tap_dropped":
        real = ref.causal_conv
        monkeypatch.setattr(ref, "causal_conv", lambda s, kernel: real(
            s, kernel.at[0].set(0.0)))
    elif control == "epsilon_1e-2":
        return {**SIZES, "weight_eps": 1e-2}
    elif control == "no_selection_bias":
        real = ref.route
        monkeypatch.setattr(ref, "route", lambda router, bias, f, sizes: real(
            router, jnp.zeros_like(bias), f, sizes))
    else:
        real = ref.causal_conv
        monkeypatch.setattr(ref, "short_conv", lambda p, u: (lambda b, c, z: (
            b * real(c * z, p["conv_kernel"])) @ p["out_proj"]["kernel"])(
                *jnp.split(u @ p["in_proj"]["kernel"], 3, axis=-1)))
    return SIZES


@pytest.mark.parametrize("control", CONTROLS)
def test_the_reference_tells_each_wrong_model_apart(control, variables,
                                                    tokens, monkeypatch):
    """The controls the cell's ``model_loss_rtol`` is held against on the
    chip, here in f32: each moves the loss by far more than rounding (the
    epsilon, which scales every routed output by 1 % alike, by the least)."""
    params, state = variables
    want = float(ref.loss(SIZES, params, state, tokens))
    wrong = float(ref.loss(altered(control, monkeypatch), params, state,
                           tokens))
    least = 2e-6 if control == "epsilon_1e-2" else 1e-4
    assert abs(wrong - want) / want > least, (control, wrong, want)


# ---- scopes and counters --------------------------------------------------------

SCOPES = ("bf.sconv.project", "bf.sconv.gate_conv", "bf.attn.project",
          "bf.attn.rotary", "bf.moe.route", "bf.moe.dispatch",
          "bf.moe.experts", "bf.moe.combine", "bf.mlp.dense",
          "bf.head.logits", "bf.embed.lookup", "bf.block.norm")


@pytest.mark.parametrize("remat", [False, True], ids=["stored", "remat"])
def test_scopes_the_benchmark_reads_reach_the_compiled_step_unnested(
        remat, variables, tokens):
    """Every scope of the new layers reaches the compiled text, in the
    forward, the backward and (under remat) the recomputed pass; no op sits
    under two; every heavy op carries one; and ``phases/step_conv.json``
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
    for scope in ("bf.sconv.project", "bf.sconv.gate_conv"):
        assert scope in by_pass["backward"], scope
        assert (scope in by_pass["recompute"]) == remat, scope
    rules = json.load(open(os.path.join(
        REPO, "chipbench", "phases", "step_conv.json")))["rules"]

    def phase_of(scope):
        return next(phase for phase, field, pattern in rules
                    if field == "op_name" and re.search(pattern, scope))

    assert phase_of("bf.sconv.project") == "short_conv_project"
    assert phase_of("bf.sconv.gate_conv") == "conv_gate"
    for scope in ("bf.moe.route", "bf.moe.dispatch", "bf.moe.combine"):
        assert phase_of(scope) == "expert_dispatch"
    assert phase_of("bf.moe.experts") == "expert_ffn"
    for scope in ("bf.attn.project", "bf.attn.rotary"):
        assert phase_of(scope) == "attention_project"
    # the accepted rows keep their order around the new ones
    step = json.load(open(os.path.join(
        REPO, "chipbench", "phases", "step.json")))["rules"]
    assert [r for r in rules if r in step] == step
    assert [r[0] for r in rules].index("expert_dispatch") < [
        r[0] for r in rules].index("recompute")


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
        assert snap["bf_sconv_calls_total"] == 4
        assert snap["bf_attn_full_calls_total"] == 1
        assert "bf_attn_window_calls_total" not in snap
        assert snap["bf_moe_assignments_total"] == 4 * 2 * 20 * K
        assert 0 < snap["bf_moe_assignments_held_total"] < 4 * 2 * 20 * K
    finally:
        registry.metrics_stop()
        registry._STOPPED = False


# ---- what GPTConfig refuses -----------------------------------------------------

@pytest.mark.parametrize("over", [
    dict(short_conv=None),                               # needs its sizes
    dict(layer_types=(FULL,) * 5),                       # sizes, no layer
    dict(layer_types=(CONV,) * 5),                       # qk_norm, no layer
    dict(layer_types=KINDS[:4] + ("kda",)),              # two families
    dict(layer_types=KINDS[:4] + ("mamba",)),
    dict(layer_types=KINDS[:4] + ("conv",)),             # the source's name
    dict(attention="fused_qkv"), dict(grouped=None),
    dict(position="learned"), dict(position="rotary"),
    dict(heads_held=(0, 2)),                             # every head is held
    dict(experts=experts(weight_eps=-1e-6)),
    dict(experts=experts(weight_eps=1e-6, router="softmax_topk")),
], ids=["no_sizes", "sizes_without_layers", "qk_norm_without_attention",
        "with_kda", "with_mamba", "unknown_type", "fused_qkv",
        "no_grouped_sizes", "learned", "rotary_everywhere", "heads_held",
        "negative_epsilon", "epsilon_under_softmax"])
def test_config_refuses_what_it_cannot_build(over):
    with pytest.raises(ValueError):
        config(**over)


def test_qk_norm_and_short_conv_belong_to_the_grouped_query_layers():
    """``qk_norm`` outside them has nowhere to be stated (the sizes are
    refused without ``attention='grouped_query'``; the latent layers keep
    their own flag for their own layer types), and any feed-forward goes
    with a ``short_conv`` layer."""
    with pytest.raises(ValueError, match="come together"):
        GPTConfig(grouped=grouped())                     # fused_qkv heads
    with pytest.raises(ValueError, match="latent_attention"):
        GPTConfig(attention="latent", position="rotary", norm="rmsnorm",
                  latent=LatentSizes(qk_norm=True))
    with pytest.raises(ValueError, match="short_conv"):
        GPTConfig(short_conv=ShortConvSizes())           # no such layer
    for ffn in (dict(ffn="gelu"), dict(ffn="swiglu", ffn_width=96)):
        cfg = config(experts=None, **ffn)
        assert cfg.layer_types == KINDS
    conv_only = config(layer_types=(CONV,) * 5,
                       grouped=grouped(qk_norm=False))
    assert conv_only.short_conv.taps == 3


def test_a_block_builds_the_mixer_its_type_names():
    x, positions = rand((1, 8, 64), 2), jnp.arange(8)[None]
    for kind, mixer in ((CONV, "conv"), (FULL, "attn")):
        block = Block(config(), mixer=kind)
        made = block.init(jax.random.PRNGKey(0), x, dense_attention,
                          positions)
        assert set(made["params"]) == {"ln1", mixer, "ln2", "moe"}, kind
    dense = Block(config(), mixer=CONV, ffn="swiglu").init(
        jax.random.PRNGKey(0), x, dense_attention, positions)
    assert set(dense["params"]) == {"ln1", "conv", "ln2", "mlp"}


# ---- the benchmark's configuration ----------------------------------------------

CELL = "lfm2moe.t8192.solo"
CATALOG = {   # the `config` of the catalog's LFM2-8B-A1B row, every number
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
LAYER_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv", "conv", "conv", "full_attention",
               "conv", "conv", "conv", "full_attention", "conv", "conv",
               "conv", "full_attention", "conv", "conv", "full_attention",
               "conv", "conv"]
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size"]


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
    assert deployment["published"]["layer_types"] == LAYER_TYPES
    assert cfg_file["layer_types"] == LAYER_TYPES[1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert deployment["chips_sharing_a_layer"] == 4
    assert deployment["router_outputs"] == 32
    assert (deployment["experts_held_first"], deployment["experts_held"],
            cfg_file["num_experts"]) == (0, 8, 8)
    assert deployment["vocabulary_shards"] == 4
    assert cfg_file["vocab_size"] * 4 == 65536
    assert (deployment["first_layer"], cfg_file["num_dense_layers"]) == (1, 1)
    assert deployment["router_trains"] is False
    for key in ("tie_word_embeddings", "in_proj_order", "conv", "qk_norm",
                "rotary_pairing", "router", "router_precision",
                "selection_bias", "router_gradient", "initialisers",
                "optimizer", "compute_dtype"):
        assert key in cfg_file["assumed"], key
    assert "8.34 B" in cfg_file["assumed"]["tie_word_embeddings"]
    entry = manifest.entry("configs", "lfm2-8b-a1b")
    assert entry["source"] == cfg_file["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert entry["reduced"] == REDUCED
    assert (traffic["seq_len"], traffic["batch"], traffic["remat"]) == (
        8192, 4, True)
    cfg = family.model.cfg
    assert cfg.layer_types == (CONV, FULL, CONV, CONV, CONV)
    assert cfg.grouped == GroupedSizes(kv_heads=8, head_dim=64,
                                       window=128000, rope_theta=1e6,
                                       qk_norm=True)
    assert cfg.short_conv == ShortConvSizes(taps=3)
    assert cfg.experts == ExpertSizes(
        num_experts=32, top_k=4, width=1792, num_shared=0, scale=1.0,
        held=(0, 8), first_dense=1, router="sigmoid_noaux_tc",
        activation="silu", router_input="ffn", train_router=False,
        weight_eps=1e-6)
    assert (cfg.hidden_size, cfg.num_heads, cfg.ffn_width, cfg.norm_eps) == (
        2048, 32, 7168, 1e-5)
    assert cfg.remat and cfg.tie_head and cfg.dtype == jnp.bfloat16


def test_the_parameter_count_is_the_files_and_the_issue_s_table(published):
    _, cfg_file, _, family = published
    shapes, state = jax.eval_shape(family.init, jax.random.PRNGKey(0))
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == cfg_file["parameters"] == 507_820_160
    sizes = {name: {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
                    for k, v in shapes[name].items()}
             for name in ("block_0", "block_1", "block_2")}
    conv = 2048 * 3 * 2048 + 2048 * 2048 + 3 * 2048          # 16,783,360
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64     # 10,485,888
    moe = 2048 * 32 + 8 * 3 * 2048 * 1792                     # 88,145,920
    assert sizes["block_0"] == {"conv": conv, "mlp": 3 * 2048 * 7168,
                                "ln1": 2048, "ln2": 2048}
    assert sizes["block_1"] == {"attn": attention, "moe": moe,
                                "ln1": 2048, "ln2": 2048}
    assert sizes["block_2"] == {"conv": conv, "moe": moe,
                                "ln1": 2048, "ln2": 2048}
    assert shapes["tok"]["embedding"].shape == (16_384, 2_048)
    assert "lm_head" not in shapes
    assert set(state["buffers"]) == {f"block_{i}" for i in range(1, 5)}
    # the uncut model by the same parts: the published 8.3B with one table
    whole = (22 * (2048 * 32 + 32 * 3 * 2048 * 1792) + 2 * 3 * 2048 * 7168
             + 18 * conv + 6 * attention + 65536 * 2048 + 49 * 2048)
    assert 8.33e9 < whole < 8.35e9 < whole + 65536 * 2048


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("norm_topk_prob", False), ("use_expert_bias", False),
    ("tie_word_embeddings", False), ("model_type", "lfm2"),
    ("num_dense_layers", 2), ("layer_types", ["conv"] * 5),
    ("layer_types", ["conv", "sliding_attention", "conv", "conv", "conv"])])
def test_family_refuses_what_it_does_not_compute(published, key, value):
    manifest, cfg_file, _, _ = published
    build = manifest.module("families", "conv_gqa_moe").build
    config_file = {**cfg_file, key: value}
    if key == "layer_types" and "sliding_attention" in value:
        config_file["deployment"] = {**cfg_file["deployment"], "published": {
            **cfg_file["deployment"]["published"],
            "layer_types": ["conv"] + value}}
    with pytest.raises(SystemExit):
        build(config_file, {"seq_len": 64, "batch": 1, "remat": True})


def tiny_manifest(tmp_path):
    """A manifest written here around the tiny configuration that exists
    only under ``tests/data``: one cell, ``tinylfm2.solo``."""
    data = os.path.join(REPO, "tests", "data", "conv_gqa_moe")
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "t40.b2.remat.solo.json").write_text(json.dumps({
        "ranks": 1, "batch": 2, "seq_len": 40, "remat": True,
        "comm": "neighbor", "topology": "ExponentialTwoGraph",
        "backend": "auto"}))
    manifest_path = tmp_path / "BENCHMARK.json"
    manifest_path.write_text(json.dumps({
        "paths": [str(tmp_path), "chipbench"],
        "configs": [{"name": "tiny-lfm2",
                     "file": os.path.join(data, "tiny-lfm2.json")}],
        "workloads": [{"name": "tinylfm2.solo", "config": "tiny-lfm2",
                       "traffic": "t40.b2.remat.solo", "chips": 1}]}))
    return str(manifest_path)


@pytest.mark.duration_budget(90)   # compiles init, step, the reference's
# step and the two model-loss evaluations, as test_gqa_moe's twin
def test_the_family_runs_through_the_harness_and_agrees(tmp_path):
    """``cell.build_cell`` and three steps of ``run.py::agreement`` on a
    virtual CPU device."""
    from chipbench import cell as cells
    from chipbench import run

    manifest = cells.Manifest.load(tiny_manifest(tmp_path))
    cell = cells.build_cell(manifest, "tinylfm2.solo", seed=2147483659)
    cfg = cell.family.model.cfg
    assert cfg.layer_types == (CONV, FULL, CONV, CONV, CONV)
    assert cfg.experts.held == (4, 4) and cfg.experts.num_experts == 8
    assert cfg.experts.first_dense == 1 and cfg.tie_head
    state, cell.state = cell.state, None
    for k in range(2):                                   # as the warm-up
        state, loss = cell.step(state, cell.ring[k])
    report = {}
    ok, leaves, loss_err = run.agreement(cell, state, 2, report)
    assert ok, (leaves[:3], loss_err, report)
    assert loss_err < 1e-4
    assert report["model_loss"]["rel_err"] < 1e-4
    assert report["model_loss"]["reference"] > 1.0       # ln(250) = 5.5


@pytest.mark.duration_budget(120)   # the cell, the reference's step twice,
# the plain model three times
def test_the_controls_script_tells_a_wrong_step_and_a_wrong_model(tmp_path,
                                                                  capsys):
    """``benchmarks/conv_gqa_moe_controls.py``, which read the cell's
    controls on the chip (PERF.md section 6, PR 43), on the tiny cell: the
    reference at 1.25 x the rate, the reference stepping a model without
    its q/k norms (whose scales then stand still) and the plain model
    without its rotary do not agree, the pair as it is does."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import conv_gqa_moe_controls

    conv_gqa_moe_controls.main([
        "--manifest", tiny_manifest(tmp_path), "--workload", "tinylfm2.solo",
        "--seed", "2147483801", "--preroll", "4", "--controls",
        "lr_1.25,step_no_qk_norm,none,no_rotary,router_bf16",
        "--out", str(tmp_path / "out")])
    said = {}
    for line in capsys.readouterr().out.splitlines():
        kind, _, fields = line.partition(" ")
        if kind in ("AGREEMENT", "MODEL_LOSS"):
            fields = json.loads(fields)
            said[fields["control"]] = fields
    assert not said["lr_1.25"]["ok"]
    assert set(said["lr_1.25"]["groups"]) == {
        "embedding", "scale", "router", "experts", "attn", "conv", "mlp"}
    assert not said["step_no_qk_norm"]["ok"]

    def q_norm_moved(control):     # the reference's scale against the system's
        with open(tmp_path / "out" / f"{control}.seed2147483801.json") as f:
            return max(d for name, d, _ in json.load(f)["leaves"]
                       if "['q_norm']" in name)
    assert q_norm_moved("step_no_qk_norm") > 2 * q_norm_moved("lr_1.25")
    assert said["none"]["ok"] and not said["no_rotary"]["ok"]
    assert said["router_bf16"]["rel_err"] > said["none"]["rel_err"]


@pytest.mark.duration_budget(60)    # the cell, its step twice, the
# reference's step twice
def test_the_probe_says_where_the_step_and_the_reference_part(tmp_path,
                                                              capsys):
    """``benchmarks/step_vs_reference.py``, which read on the chip that the
    cell's two programs repeat themselves, agree on the first loss to the
    bit and part in the backward pass (PERF.md section 6, PR 43), on the
    tiny cell: the same three answers, every leaf listed."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import step_vs_reference

    step_vs_reference.main([
        "--manifest", tiny_manifest(tmp_path), "--workload", "tinylfm2.solo",
        "--seed", "2147483801", "--preroll", "4"])
    losses, compared = {}, {}
    for line in capsys.readouterr().out.splitlines():
        kind, _, fields = line.partition(" ")
        if kind == "LOSS":
            fields = json.loads(fields)
            losses[fields["program"]] = fields
        elif kind == "COMPARE":
            fields = json.loads(fields)
            compared[fields["tag"]] = fields
    assert losses["system"]["first"] == losses["system"]["again"]
    assert losses["reference"]["first"] == losses["reference"]["again"]
    assert losses["reference"]["equals_the_system_s"]
    assert compared["system_vs_itself"]["elements_differing"] == 0
    assert compared["reference_vs_itself"]["elements_differing"] == 0
    between = compared["system_vs_reference"]["leaves"]
    assert len(between) == len(jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: TransformerLM(config()).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])))
    assert max(leaf[3] for leaf in between) < 1e-6       # last bits, f32
