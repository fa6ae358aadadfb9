"""The linear-attention / latent-attention hybrid (``GPTConfig.layer_types``
of ``kda`` and ``latent_attention``: Kimi Delta Attention beside latent
attention without a query bottleneck, q/k norms, head-wise output gates,
group-limited sigmoid routing, a chip's share of the heads and of the
experts) against the plain reference
``chipbench/linear_latent_moe_reference.py``: tiny widths, f32, seeded
random weights, on the CPU."""

import dataclasses
import functools
import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bluefog_tpu.models.transformer import (  # noqa: E402
    Block, ExpertSizes, GPTConfig, KdaMixer, KdaSizes, LatentAttention,
    LatentSizes, RoutedFFN, TransformerLM, next_token_loss)
from bluefog_tpu.ops import local_attention  # noqa: E402
from bluefog_tpu.ops.moe import (  # noqa: E402
    routed_experts, sigmoid_topk_router)
from chipbench import linear_latent_moe_reference as ref  # noqa: E402

KDA, MLA = "kda", "latent_attention"
KINDS = (KDA, KDA, MLA, KDA)
VOCAB, HIDDEN, HEADS = 96, 64, 4
LATENT = LatentSizes(q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=8,
                     qk_rope_head_dim=4, v_head_dim=8, rope_theta=1e4,
                     qk_norm=True, head_gate=True)
EXPERTS = ExpertSizes(num_experts=16, top_k=4, width=32, num_shared=1,
                      scale=2.5, held=(4, 4), first_dense=1, n_group=4,
                      topk_group=2)
SIZES = {"kinds": KINDS, "head_dim": 8, "lower_bound": -5.0, "qk_nope": 8,
         "qk_rope": 4, "rope_theta": 1e4, "eps": 1e-6, "top_k": 4,
         "scale": 2.5, "n_group": 4, "topk_group": 2, "held_first": 4}


def config(**over):
    return GPTConfig(**{**dict(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=len(KINDS),
        num_heads=HEADS, dtype=jnp.float32, attention="latent",
        ffn="routed+shared", norm="rmsnorm", position="rotary", ffn_width=96,
        norm_eps=1e-6, latent=LATENT, kda=KdaSizes(head_dim=8),
        experts=EXPERTS, layer_types=KINDS), **over})


def shaken(params, seed=5, scale=0.05):
    """Every leaf moved off its initial value, so that unit scales, the
    zero-mean taps and ``A_log`` all carry a gradient worth comparing."""
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


def dense_attention(q, k, v, **mask):
    return local_attention(q, k, v, causal=True, backend="dense", **mask)


# ---- the two mixers against the reference ----------------------------------

def mixer(kind, cfg):
    return (KdaMixer(cfg) if kind == KDA else LatentAttention(cfg))


@functools.partial(jax.jit, static_argnums=(0, 1))
def apply_mixer(kind, cfg, params, y):
    positions = jnp.arange(y.shape[1])[None]
    if kind == KDA:
        return KdaMixer(cfg).apply({"params": params}, y)
    return LatentAttention(cfg).apply({"params": params}, y, dense_attention,
                                      positions)


def init_mixer(kind, cfg, y, seed=0):
    positions = jnp.arange(y.shape[1])[None]
    args = (y,) if kind == KDA else (y, dense_attention, positions)
    return shaken(jax.jit(lambda key: mixer(kind, cfg).init(key, *args))(
        jax.random.PRNGKey(seed))["params"], scale=0.1)


@pytest.mark.duration_budget(60)   # the scan's backward, compiled twice
@pytest.mark.parametrize("held", [None, (1, 2)], ids=["all_heads", "a_share"])
@pytest.mark.parametrize("kind", [KDA, MLA])
def test_mixer_matches_the_reference_in_value_and_gradient(kind, held):
    cfg = config(heads_held=held)
    y = rand((2, 70, HIDDEN), 3)        # more than a chunk of the scan
    params = init_mixer(kind, cfg, y)
    heads = HEADS if held is None else held[1]
    assert params["o"]["kernel"].shape == (heads * 8, HIDDEN)
    plain = (lambda p, y: ref.kda(p, y, SIZES)) if kind == KDA else (
        lambda p, y: ref.mla(p, y, jnp.arange(y.shape[1]), SIZES))
    probe = rand((2, 70, HIDDEN), 4)
    got = jax.jit(jax.value_and_grad(
        lambda p, y: jnp.sum(apply_mixer(kind, cfg, p, y) * probe),
        (0, 1)))(params, y)
    want = jax.jit(jax.value_and_grad(
        lambda p, y: jnp.sum(plain(p, y) * probe), (0, 1)))(params, y)
    assert_trees_close(got, want)


def head_share(kind, params, first, count, width):
    """The columns of every projection out of the model width, and the rows
    of the output projection, of heads ``first .. first + count - 1``; what
    every head reads stays whole."""
    def columns(kernel, per_head):
        return kernel[..., first * per_head:(first + count) * per_head]

    share = dict(params)
    per_head = {"q": 12 if kind == MLA else width, "k": width, "v": width,
                "f": width, "b": 1, "head_gate": 1, "kv_up": 2 * width}
    for name, per in per_head.items():
        if name in params:
            share[name] = {"kernel": columns(params[name]["kernel"], per)}
    for name in ("q_conv", "k_conv", "v_conv", "dt_bias"):
        if name in params:
            share[name] = columns(params[name], width)
    if "A_log" in params:
        share["A_log"] = params["A_log"][first:first + count]
    share["o"] = {"kernel": params["o"]["kernel"][
        first * width:(first + count) * width]}
    return share


@pytest.mark.parametrize("kind", [KDA, MLA])
def test_the_two_head_shares_add_up_to_the_uncut_layer(kind):
    """Heads 0..1 and heads 2..3 of a four-head layer, each built as what a
    chip of the tensor-parallel pair holds (``heads_held``) from the uncut
    layer's own parameters: their outputs sum to the uncut layer's, in the
    system and against the plain reference of the whole layer."""
    y = rand((2, 40, HIDDEN), 11)
    whole = init_mixer(kind, config(), y, seed=2)
    total = apply_mixer(kind, config(), whole, y)
    parts = [apply_mixer(kind, config(heads_held=(first, 2)),
                         head_share(kind, whole, first, 2, 8), y)
             for first in (0, 2)]
    np.testing.assert_allclose(parts[0] + parts[1], total, atol=2e-5)
    plain = ref.kda(whole, y, SIZES) if kind == KDA else ref.mla(
        whole, y, jnp.arange(40), SIZES)
    np.testing.assert_allclose(parts[0] + parts[1], plain, atol=5e-5)
    assert float(jnp.max(jnp.abs(parts[0]))) > 1e-3      # a real share


# ---- the expert layer -------------------------------------------------------

def plain_masked_topk(steer, top_k, n_group, topk_group):
    """A plain top-k over masked groups in numpy: stable descending orders,
    so a tie goes to the lower index."""
    steer = np.asarray(steer, np.float64)
    t, e = steer.shape
    grouped = steer.reshape(t, n_group, e // n_group)
    score = -np.sort(-grouped, axis=-1)[..., :2].sum(-1)
    kept = np.argsort(-score, axis=-1, kind="stable")[:, :topk_group]
    masked = np.full_like(grouped, -np.inf)
    for row in range(t):
        masked[row, kept[row]] = grouped[row, kept[row]]
    return np.argsort(-masked.reshape(t, e), axis=-1,
                      kind="stable")[:, :top_k]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_selection_is_a_top_k_over_the_kept_groups(seed):
    x, kernel = rand((50, 24), seed), rand((24, 32), seed + 10)
    bias = rand((32,), seed + 20, 0.3)
    idx, weights = sigmoid_topk_router(x, kernel, bias, top_k=6, scale=2.5,
                                       n_group=4, topk_group=2)
    s = jax.nn.sigmoid(jnp.dot(x, kernel, precision="highest"))
    want = plain_masked_topk(s + bias, 6, 4, 2)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want, -1))
    assert len({int(i) // 8 for i in idx[0]}) <= 2       # two groups of 8
    chosen = jnp.take_along_axis(s, idx, -1)
    np.testing.assert_allclose(
        weights, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    # the reference's mask names the same experts
    mask = np.asarray(ref.chosen_experts(s + bias, 6, 4, 2))
    assert (mask.sum(-1) == 6).all()
    assert np.take_along_axis(mask, np.asarray(idx), -1).all()


def test_ties_at_a_group_s_edge_go_to_the_lower_index():
    """Scores set by hand through the bias (the router's weights are zero,
    so ``s = 0.5`` everywhere).  Groups 1 and 2 tie for the second place
    among the groups: group 1 is kept.  Inside the kept groups the experts
    tie for the last places: the lower indices are taken, across the edge
    between groups 0 and 1."""
    bias = jnp.array([0.9, 0.5, 0.5, 0.1,      # group 0: score 2.4
                      0.6, 0.5, 0.5, 0.5,      # group 1: 2.1
                      0.6, 0.5, 0.0, 0.0,      # group 2: 2.1, a tie with 1
                      0.5, 0.5, 0.5, 0.5])     # group 3: 2.0
    x, kernel = jnp.ones((3, 5)), jnp.zeros((5, 16))
    idx, weights = sigmoid_topk_router(x, kernel, bias, top_k=5, scale=1.0,
                                       n_group=4, topk_group=2)
    # 0.9, 0.6, then 0.5 four times over: experts 1, 2 (group 0) and 5, the
    # first of group 1's
    np.testing.assert_array_equal(np.sort(idx, -1), [[0, 1, 2, 4, 5]] * 3)
    np.testing.assert_array_equal(
        plain_masked_topk(0.5 + bias[None], 5, 4, 2)[0], [0, 4, 1, 2, 5])
    np.testing.assert_allclose(weights, 0.2, rtol=1e-6)
    mask = ref.chosen_experts(0.5 + bias[None], 5, 4, 2)
    np.testing.assert_array_equal(np.flatnonzero(mask[0]), [0, 1, 2, 4, 5])


def router_of_pr_28(x, router_kernel, bias, *, top_k, scale):
    """``sigmoid_topk_router`` as it stood before it learnt of groups."""
    with jax.named_scope("bf.moe.route"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router_kernel.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, idx = lax.top_k(s + lax.stop_gradient(bias.astype(jnp.float32)),
                           top_k)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        weights = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx, weights


@pytest.mark.parametrize("groups", [{}, {"n_group": 1, "topk_group": 1}],
                         ids=["default", "one_group"])
def test_one_group_is_today_s_router_bit_for_bit(groups):
    """``n_group = topk_group = 1`` (the default, ``joyai-llm-flash``'s):
    the same indices, the same weights and the same gradient to the bit:
    the same program, instruction for instruction."""
    x, kernel = rand((64, 32), 1), rand((32, 48), 2)
    bias = rand((48,), 3, 0.2)

    def outcome(router, **kw):
        def weighted(x, kernel):
            idx, weights = router(x, kernel, bias, top_k=8, scale=2.5, **kw)
            return jnp.sum(weights * jnp.cos(idx)), (idx, weights)
        return jax.jit(jax.value_and_grad(weighted, (0, 1), has_aux=True))

    got = outcome(sigmoid_topk_router, **groups)(x, kernel)
    want = outcome(router_of_pr_28)(x, kernel)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)
    assert (outcome(sigmoid_topk_router, **groups).lower(x, kernel).as_text()
            == outcome(router_of_pr_28).lower(x, kernel).as_text())


WIDE = ExpertSizes(num_experts=512, top_k=8, width=16, num_shared=1,
                   scale=2.5, held=(0, 512), first_dense=0, n_group=8,
                   topk_group=4)
WIDE_SIZES = {**SIZES, "top_k": 8, "n_group": 8, "topk_group": 4}


@pytest.fixture(scope="module")
def uncut_layer():
    """512 experts in 8 groups of 64 (4 kept, top 8), as the deployment's,
    at tiny widths: input, parameters, buffers and the router's outcome,
    which is every chip's alike."""
    y = rand((1, 24, 32), 21)
    whole = RoutedFFN(config(hidden_size=32, experts=WIDE)).init(
        jax.random.PRNGKey(3), y)
    params, buffers = shaken(whole["params"], scale=0.1), whole["buffers"]
    idx, weights = sigmoid_topk_router(
        y.reshape(-1, 32), params["router"], buffers["selection_bias"],
        top_k=8, scale=2.5, n_group=8, topk_group=4)
    return y, params, buffers, idx, weights


def experts_of(params, first, count):
    return {**params, **{name: params[name][first:first + count]
                         for name in ("w_gate", "w_up", "w_down")}}


@pytest.mark.parametrize("chips", [range(0, 16), range(16, 32),
                                   range(32, 48), range(48, 64)],
                         ids=lambda chips: f"chips{chips[0]}to{chips[-1]}")
def test_sixteen_chips_shares_add_up_to_their_experts_part(chips,
                                                           uncut_layer):
    """64 chips hold 8 of the 512 experts each.  The routed parts that 16
    of them compute (``held = (8 * chip, 8)``) sum to what the plain
    reference gives for those chips' 128 experts; the held shares sum to
    the share of the assignments that went to them."""
    y, params, buffers, idx, weights = uncut_layer
    flat = y.reshape(-1, 32)
    total, held = jnp.zeros_like(flat), 0.0
    for chip in chips:
        mine = experts_of(params, 8 * chip, 8)
        part, record = routed_experts(
            flat, idx, weights, mine["w_gate"], mine["w_up"], mine["w_down"],
            num_experts=512, held=(8 * chip, 8))
        total, held = total + part, held + float(record["held_share"])
    first = 8 * chips[0]
    want = jax.jit(lambda p: ref.expert_layer(
        p, buffers["selection_bias"], y, {**WIDE_SIZES, "held_first": first})
        - ref.gated_mlp(p["shared"], y))(experts_of(params, first, 128))
    np.testing.assert_allclose(total.reshape(y.shape), want, atol=2e-5)
    in_range = (idx >= first) & (idx < first + 128)
    assert abs(held - float(jnp.mean(in_range))) < 1e-6


def test_the_64_expert_shares_add_up_to_the_uncut_expert_layer(uncut_layer):
    """The four sixteenths above, with the shared expert counted once, are
    the uncut layer; and a share through the module is that chip's routed
    part plus the shared expert."""
    y, params, buffers, idx, weights = uncut_layer
    bias = buffers["selection_bias"]
    shared = ref.gated_mlp(params["shared"], y)
    parts = [jax.jit(lambda p, first=first: ref.expert_layer(
        p, bias, y, {**WIDE_SIZES, "held_first": first}))(
            experts_of(params, first, 128)) - shared
             for first in range(0, 512, 128)]
    uncut = jax.jit(lambda p: ref.expert_layer(
        p, bias, y, {**WIDE_SIZES, "held_first": 0}))(params)
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=2e-5)
    assert float(jnp.max(jnp.abs(uncut - shared))) > 1e-2
    for chip in (0, 63):
        mine = experts_of(params, 8 * chip, 8)
        out = RoutedFFN(config(hidden_size=32, experts=dataclasses.replace(
            WIDE, held=(8 * chip, 8)))).apply(
                {"params": mine, "buffers": buffers}, y)
        part, _ = routed_experts(
            y.reshape(-1, 32), idx, weights, mine["w_gate"], mine["w_up"],
            mine["w_down"], num_experts=512, held=(8 * chip, 8))
        np.testing.assert_allclose(out, part.reshape(y.shape) + shared,
                                   atol=1e-6)


# ---- the whole model ----------------------------------------------------------

@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 71), 0, VOCAB)


@pytest.fixture(scope="module")
def variables(tokens):
    model = TransformerLM(config(heads_held=(0, 2)))
    made = jax.jit(model.init)(jax.random.PRNGKey(0), tokens[:, :16])
    return shaken(made["params"]), {"buffers": made["buffers"]}


@pytest.mark.duration_budget(60)   # the first compile of the plain reference
@pytest.mark.parametrize("remat", [False, True], ids=["stored", "remat"])
def test_model_loss_and_gradients_match_the_reference(remat, tokens,
                                                      variables):
    params, state = variables
    model = TransformerLM(config(heads_held=(0, 2), remat=remat))
    got = jax.jit(jax.value_and_grad(lambda p: next_token_loss(
        model, p, state, tokens)))(params)
    want = jax.jit(jax.value_and_grad(lambda p: ref.loss(
        SIZES, p, state, tokens)))(params)
    assert abs(float(got[0]) - float(want[0])) < 2e-5 * float(want[0])
    assert_trees_close(got[1], want[1], tol=2e-4)


def test_a_router_that_does_not_train_takes_no_gradient(tokens, variables):
    params, state = variables
    model = TransformerLM(config(heads_held=(0, 2), experts=dataclasses.replace(
        EXPERTS, train_router=False)))
    grads = jax.jit(jax.grad(lambda p: next_token_loss(
        model, p, state, tokens)))(params)
    assert float(jnp.max(jnp.abs(grads["block_1"]["moe"]["router"]))) == 0.0
    assert float(jnp.max(jnp.abs(grads["block_1"]["moe"]["w_up"]))) > 0.0


@pytest.mark.parametrize("control", ["scalar_decay", "another_lower_bound",
                                     "ungrouped_routing", "no_key_norm"])
def test_the_reference_tells_each_wrong_model_apart(control, tokens,
                                                    variables, monkeypatch):
    """The plain model changed in one place no longer gives the system's
    loss: what ``model_loss_rtol`` guards on the chip."""
    params, state = variables
    sizes = dict(SIZES)
    if control == "scalar_decay":       # one decay a head, not a channel
        rule = ref.delta_rule
        monkeypatch.setattr(ref, "delta_rule", lambda q, k, v, g, beta: rule(
            q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape),
            beta))
    elif control == "another_lower_bound":
        sizes["lower_bound"] = -1.0
    elif control == "ungrouped_routing":
        sizes.update(n_group=1, topk_group=1)
    else:
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.ones_like(leaf)
            if "k_head_norm" in jax.tree_util.keystr(path) else leaf, params)
    model = TransformerLM(config(heads_held=(0, 2)))
    got = float(jax.jit(lambda p: next_token_loss(model, p, state, tokens))(
        variables[0]))
    wrong = float(jax.jit(lambda p: ref.loss(sizes, p, state, tokens))(
        params))
    assert abs(wrong - got) > 1e-4 * got, (control, wrong, got)


@pytest.mark.duration_budget(40)    # a model initialised and lowered anew
@pytest.mark.parametrize("heads", ["narrow", "a_lane_tile"])
def test_scopes_and_kernel_names_the_benchmark_reads_reach_the_step(
        heads, tokens, variables, monkeypatch):
    """With heads a lane tile wide and whole tiles of tokens the
    convolutions run in their kernels (interpreted here), by the names a
    trace shows and under the convolution's scope."""
    model, (params, state) = (
        TransformerLM(config(heads_held=(0, 2), remat=True)), variables)
    if heads == "a_lane_tile":
        run_the_convolution_s_kernels(monkeypatch)
        model, (params, state) = wide_heads(remat=True)
        tokens = tokens[:, :49]
    text = jax.jit(jax.grad(lambda p: next_token_loss(
        model, p, state, tokens))).lower(params).as_text(debug_info=True)
    for scope in ("bf.kda.project", "bf.kda.conv", "bf.kda.scan",
                  "bf.kda.norm_gate", "bf.mla.project", "bf.moe.route"):
        assert scope in text, scope
    for outer in ("bf.kda.project", "bf.kda.conv", "bf.kda.norm_gate"):
        assert f"{outer}/bf." not in text            # leaf-level, unnested
    for kernel, call in (("bf_cconv_fwd", "jit(_silu_forward)"),
                         ("bf_cconv_bwd", "jit(_silu_backward)")):
        sites = [line for line in text.splitlines() if call in line]
        assert (kernel in text and bool(sites)) == (heads == "a_lane_tile")
        assert all("/bf.kda.conv/" + call in line for line in sites), sites


# ---- the convolutions' kernels, and the mixer before them ---------------------

class MixerBeforeTheKernels(KdaMixer):
    """``KdaMixer`` as PR 41 wrote it: each projection's output cast to f32,
    convolved and put through SiLU in ``jax.numpy``, q and k normalised a
    head under the projections' scope, one cast on the way into the scan."""

    @nn.compact
    def __call__(self, y):
        from bluefog_tpu.models import transformer as tr

        cfg, sizes, h = self.cfg, self.cfg.kda, tr._heads(self.cfg)
        d, lead, f32 = sizes.head_dim, y.shape[:-1], jnp.float32
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)
        taps = tr._uniform_within(sizes.conv ** -0.5)
        projected = [dense(h * d, name=name)(y) for name in "qkv"]
        decay = dense(h * d, name="f")(y)
        beta = jax.nn.sigmoid(dense(h, name="b")(y).astype(f32))
        gate = dense(h, name="head_gate")(y)
        q, k, v = (nn.silu(tr.causal_depthwise_conv(
            x.astype(f32), self.param(f"{name}_conv", taps,
                                      (sizes.conv, h * d), f32),
            0.0)).reshape(lead + (h, d)) for name, x in zip("qkv", projected))
        q = (q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
             * d ** -0.5).astype(cfg.dtype)
        k = (k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
             ).astype(cfg.dtype)
        rate = jnp.exp(self.param("A_log", tr._a_log_init, (h,), f32))
        bias = self.param("dt_bias", tr._step_bias_init(), (h * d,), f32)
        g = sizes.lower_bound * jax.nn.sigmoid(
            rate[:, None] * (decay.astype(f32) + bias).reshape(
                lead + (h, d)))
        o = tr.kda(q, k, v.astype(cfg.dtype), g, beta)
        o = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=f32, name="o_norm")(o)
        o = tr._head_gate(o, gate)
        return dense(cfg.hidden_size, name="o")(o.reshape(lead + (h * d,)))


def wide_heads(**over):
    """The model with KDA heads a lane tile wide (two held: 256 channels a
    projection), which the convolutions' kernels tile, and its state."""
    model = TransformerLM(config(kda=KdaSizes(head_dim=128),
                                 heads_held=(0, 2), **over))
    made = jax.jit(model.init)(jax.random.PRNGKey(0),
                               jnp.zeros((2, 16), jnp.int32))
    return model, (shaken(made["params"]), {"buffers": made["buffers"]})


def run_the_convolution_s_kernels(monkeypatch, calls=None):
    from bluefog_tpu.models import transformer
    from bluefog_tpu.ops import short_conv

    def interpreted(x, *args, **kwargs):
        if calls is not None:
            calls.append((x.shape, kwargs))
        return short_conv.silu_short_conv(x, *args, **kwargs,
                                          backend="pallas_interpret")

    monkeypatch.setattr(transformer, "silu_short_conv", interpreted)


@pytest.mark.duration_budget(90)    # the model's gradient compiled three times
@pytest.mark.parametrize("remat", [False, True], ids=["stored", "remat"])
def test_the_model_on_the_convolution_s_kernels_equals_the_mixer_before_them(
        remat, tokens, monkeypatch):
    """Three tiles of 16 tokens, 256 channels a projection in heads of 128:
    q and k through ``bf_cconv_fwd`` / ``bf_cconv_bwd`` with the norm inside
    (interpreted), v through the plain pair, against the mixer as it stood
    before ``silu_short_conv``: the loss and every leaf's gradient.  Off a
    TPU ``'auto'`` is that mixer's own arithmetic, to the bit; the parameter
    tree is the one it built."""
    from bluefog_tpu.models import transformer

    model, (params, state) = wide_heads(remat=remat)
    assert params["block_0"]["attn"]["q_conv"].shape == (4, 256)

    def loss_and_grads():
        return jax.jit(jax.value_and_grad(lambda p: next_token_loss(
            model, p, state, tokens[:, :49])))(params)

    plain, plain_grads = loss_and_grads()
    calls = []
    with monkeypatch.context() as patched:
        run_the_convolution_s_kernels(patched, calls)
        got, got_grads = loss_and_grads()
    norms = [call[1]["l2norm"] for call in calls[:3]]
    assert norms == [(128, 1e-6, 128 ** -0.5), (128, 1e-6, 1.0), None]
    assert all(shape == (2, 48, 256) for shape, _ in calls) and calls
    monkeypatch.setattr(transformer, "KdaMixer", MixerBeforeTheKernels)
    want, want_grads = loss_and_grads()
    before = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, 16), jnp.int32))["params"]
    assert jax.tree_util.tree_structure(before) == (
        jax.tree_util.tree_structure(params))
    assert jax.tree_util.tree_map(jnp.shape, before) == (
        jax.tree_util.tree_map(jnp.shape, params))
    assert float(plain) == float(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(plain_grads),
                            jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert_trees_close(got_grads, want_grads)
    for name in ("q_conv", "k_conv", "v_conv"):
        assert float(jnp.max(jnp.abs(
            got_grads["block_0"]["attn"][name]))) > 0.0


def test_counters_of_the_new_layers(tokens, variables):
    from bluefog_tpu.metrics import registry

    params, state = variables
    model = TransformerLM(config(heads_held=(0, 2)))
    registry.metrics_stop()
    registry._STOPPED = False
    reg = registry.metrics_start()
    try:
        jax.block_until_ready(jax.jit(lambda p: next_token_loss(
            model, p, state, tokens))(params))
        jax.effects_barrier()
        snap = reg.snapshot()
        # three KDA layers, two sequences, two heads held, 70 tokens: 2 chunks
        assert snap["bf_kda_chunks_total"] == 3 * 2 * 2 * 2
        # a layer's three convolutions, SiLUs and two norms: one a pass
        assert snap["bf_cconv_calls_total"] == 3
        # three expert layers, 140 tokens, two groups kept a token
        assert snap["bf_moe_groups_kept_total"] == 3 * 140 * 2
        assert snap["bf_moe_assignments_total"] == 3 * 140 * 4
    finally:
        registry.metrics_stop()
        registry._STOPPED = False


# ---- what the configuration refuses -----------------------------------------

@pytest.mark.parametrize("over", [
    {"layer_types": (KDA, KDA, "full_attention", KDA)},      # two families
    {"layer_types": (KDA, KDA, "mamba", KDA)},
    {"layer_types": (KDA, KDA, "gated_delta_net", KDA)},     # no such layer
    {"kda": None},
    {"layer_types": (MLA,) * 4},                             # kda sizes, no kda
    {"position": "none"},
    {"attention": "fused_qkv", "latent": None},
    {"heads_held": (3, 2)},
    {"heads_held": (0, 0)},
    {"kda": KdaSizes(lower_bound=-8.0)},     # exp(16 * 8) is no f32
    {"kda": KdaSizes(lower_bound=0.5)},
    {"experts": dataclasses.replace(EXPERTS, n_group=3)},
    {"experts": dataclasses.replace(EXPERTS, topk_group=5)},
    {"experts": dataclasses.replace(EXPERTS, topk_group=1, top_k=6)},
    {"experts": dataclasses.replace(EXPERTS, router="softmax_topk")},
    {"mtp_depth": 1},
], ids=lambda over: ",".join(over))
def test_config_refuses_what_it_cannot_build(over):
    with pytest.raises(ValueError):
        config(**over)


def test_the_latent_layer_s_new_options_need_the_layer_types():
    base = dict(vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=2,
                num_heads=HEADS, attention="latent", position="rotary",
                norm="rmsnorm")
    GPTConfig(**base, latent=LatentSizes())                   # as PR 28
    for sizes in (LatentSizes(q_lora_rank=None), LatentSizes(qk_norm=True),
                  LatentSizes(head_gate=True)):
        with pytest.raises(ValueError):
            GPTConfig(**base, latent=sizes)
    with pytest.raises(ValueError):
        GPTConfig(**base, latent=LatentSizes(), heads_held=(0, 2))


def test_a_block_builds_the_mixer_its_type_names():
    cfg = config()
    x = rand((1, 16, HIDDEN), 2)
    positions = jnp.arange(16)[None]
    for kind, leaf in ((KDA, "A_log"), (MLA, "kv_down")):
        made = jax.jit(lambda key: Block(cfg, mixer=kind).init(
            key, x, dense_attention, positions))(jax.random.PRNGKey(0))
        assert leaf in made["params"]["attn"]
        assert "moe" in made["params"]


# ---- the configuration file and the family ----------------------------------

CELL = "ling3flash.t8192.solo"
PUBLISHED = {
    "hidden_size": 2560, "intermediate_size": 6144, "head_dim": 128,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "moe_intermediate_size": 768,
    "moe_shared_expert_intermediate_size": 768, "num_experts_per_tok": 8,
    "n_group": 8, "topk_group": 4, "routed_scaling_factor": 2.5,
    "rope_theta": 6000000, "rms_norm_eps": 1e-6, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5, "layer_group_size": 6, "q_lora_rank": None,
    "max_position_embeddings": 131072, "num_key_value_heads": 32,
    "rotary_dim": 64, "partial_rotary_factor": 0.5}


@pytest.fixture(scope="module")
def published():
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    cfg_file, traffic = cells.open_cell(manifest, CELL)
    family = manifest.module("families", cfg_file["family"]).build(
        cfg_file, traffic)
    return manifest, cfg_file, traffic, family


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_file_keeps_the_published_widths(published, key):
    assert published[1][key] == PUBLISHED[key]


def test_the_configuration_file_states_its_cuts_and_its_deployment(published):
    manifest, cfg_file, traffic, family = published
    assert cfg_file["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "num_attention_heads", "vocab_size"]
    assert set(cfg_file["changed"]) == set(cfg_file["reduced"])
    deployment = cfg_file["deployment"]
    assert deployment["chips_sharing_a_layer"] == 64
    assert deployment["tensor_parallel"] == 2
    assert deployment["vocabulary_shards"] == 8
    assert deployment["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "num_attention_heads": 32, "vocab_size": 157184}
    assert {key: cfg_file[key] for key in cfg_file["reduced"]} == {
        "num_hidden_layers": 7, "first_k_dense_replace": 1, "num_experts": 8,
        "num_attention_heads": 16, "vocab_size": 19648}
    assert cfg_file["vocab_size"] * 8 == 157184
    assert len(cfg_file["expert_swiglu_limit_list"]) == 42
    for key in ("layer_kinds", "kda_safe_gate", "group_norm_size",
                "gated_attention_proj_granularity_type", "use_qk_norm",
                "tie_word_embeddings", "selection_bias", "router_trains",
                "auxiliary_loss", "initialisers", "optimizer",
                "compute_dtype"):
        assert key in cfg_file["assumed"], key
    entry = manifest.entry("configs", "ling-3.0-flash")
    assert entry["source"] == cfg_file["source"] == (
        "https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/"
        "config.json")
    assert entry["reduced"] == cfg_file["reduced"]
    assert traffic["seq_len"] == 8192 and traffic["batch"] == 1
    cfg = family.model.cfg
    assert cfg.layer_types == (KDA, KDA, KDA, KDA, MLA, KDA, KDA)
    assert (cfg.num_heads, cfg.heads_held) == (32, (0, 16))
    assert cfg.kda == KdaSizes(head_dim=128, conv=4, lower_bound=-5.0)
    assert cfg.latent == LatentSizes(
        q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_theta=6e6, qk_norm=True,
        head_gate=True)
    assert cfg.experts == ExpertSizes(
        num_experts=512, top_k=8, width=768, num_shared=1, scale=2.5,
        held=(0, 8), first_dense=1, n_group=8, topk_group=4,
        train_router=False)
    assert deployment["router_trains"] is False
    assert cfg.remat and not cfg.tie_head and cfg.dtype == jnp.bfloat16
    assert not cfg.mtp_depth


def test_every_number_of_the_catalogued_config_is_kept_or_listed(published):
    """The published ``config.json`` as the model catalog has it: every
    number under its own key, but for the keys under ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no model catalog on this machine")
    _, cfg_file, _, _ = published
    with open(catalog) as f:
        row, = (r for r in map(json.loads, f)
                if r["source_url"] == cfg_file["source"])
    for key, value in row["config"].items():
        if key not in cfg_file["reduced"]:
            assert cfg_file[key] == value, key


def test_the_parameter_count_is_the_files(published):
    _, cfg_file, _, family = published
    shapes = jax.eval_shape(family.init, jax.random.PRNGKey(0))[0]
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == cfg_file["parameters"] == 648_850_656

    def sizes(block):
        return {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
                for k, v in shapes[block].items()}

    kda_mixer = (5 * 2560 * 2048 + 2 * 2560 * 16 + 3 * 4 * 2048 + 2048 + 16
                 + 128)
    mla_mixer = (2560 * 16 * 192 + 2560 * 576 + 512 + 512 * 16 * 256
                 + 2 * 192 + 2560 * 16 + 16 * 128 * 2560)
    moe = 2560 * 512 + 8 * 3 * 2560 * 768 + 3 * 2560 * 768
    assert sizes("block_0") == {"attn": kda_mixer, "mlp": 3 * 2560 * 6144,
                                "ln1": 2560, "ln2": 2560}
    assert sizes("block_1") == {"attn": kda_mixer, "moe": moe, "ln1": 2560,
                                "ln2": 2560}
    assert sizes("block_4")["attn"] == mla_mixer
    assert shapes["tok"]["embedding"].shape == (19_648, 2_560)
    assert shapes["lm_head"]["kernel"].shape == (2_560, 19_648)


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("use_qk_norm", False), ("kda_safe_gate", False),
    ("use_kda_lora", True), ("group_norm_size", 4),
    ("gated_attention_proj_granularity_type", "element_wise"),
    ("score_function", "softmax"), ("use_nGPT", True),
    ("kda_lower_bound", -8), ("first_k_dense_replace", 2),
    ("num_hidden_layers", 36)])        # reaches a layer with a swiglu limit
def test_family_refuses_what_it_does_not_compute(published, key, value):
    manifest, cfg_file, _, _ = published
    build = manifest.module("families", "linear_latent_moe").build
    with pytest.raises((SystemExit, ValueError)):
        build({**cfg_file, key: value}, {"seq_len": 64, "batch": 1,
                                         "remat": True})


def tiny_manifest(tmp_path):
    """A manifest written here around the tiny configuration that exists
    only under ``tests/data``: one cell, ``tinyling.solo``."""
    data = os.path.join(REPO, "tests", "data", "linear_latent_moe")
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "t40.b2.remat.solo.json").write_text(json.dumps({
        "ranks": 1, "batch": 2, "seq_len": 40, "remat": True,
        "comm": "neighbor", "topology": "ExponentialTwoGraph",
        "backend": "auto"}))
    manifest_path = tmp_path / "BENCHMARK.json"
    manifest_path.write_text(json.dumps({
        "paths": [str(tmp_path), "chipbench"],
        "configs": [{"name": "tiny-ling",
                     "file": os.path.join(data, "tiny-ling.json")}],
        "workloads": [{"name": "tinyling.solo", "config": "tiny-ling",
                       "traffic": "t40.b2.remat.solo", "chips": 1}]}))
    return str(manifest_path)


@pytest.mark.duration_budget(90)   # compiles init, step, the reference's
# step and the two model-loss evaluations, as test_latent_moe's twin
def test_the_family_runs_through_the_harness_and_agrees(tmp_path):
    """``cell.build_cell`` and three steps of ``run.py::agreement`` on a
    virtual CPU device."""
    from chipbench import cell as cells
    from chipbench import run

    manifest = cells.Manifest.load(tiny_manifest(tmp_path))
    cell = cells.build_cell(manifest, "tinyling.solo", seed=2147483659)
    cfg = cell.family.model.cfg
    assert cfg.layer_types == (KDA, MLA, KDA, KDA)   # published 1..4, of 3
    assert cfg.experts.held == (4, 4) and cfg.experts.num_experts == 16
    assert (cfg.num_heads, cfg.heads_held) == (4, (2, 2))
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
# seven evaluations of the plain model
def test_the_controls_script_tells_a_wrong_step_and_a_wrong_model(tmp_path,
                                                                  capsys):
    """``benchmarks/linear_latent_moe_controls.py``, which read the cell's
    controls on the chip (PERF.md section 6, PR 41), on the tiny cell: the
    pair as it is agrees; parameters kept in bf16, the reference at 1.25 x
    the rate and each changed plain model do not."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import linear_latent_moe_controls as controls

    controls.main(["--manifest", tiny_manifest(tmp_path), "--workload",
                   "tinyling.solo", "--seed", "2147483801", "--preroll", "2",
                   "--controls", "all"])
    lines = [line.split(" ", 1) for line in capsys.readouterr().out.splitlines()
             if line.startswith(("AGREEMENT", "MODEL_LOSS"))]
    seen = {json.loads(body)["control"]: json.loads(body)["ok"]
            for _, body in lines}
    assert set(seen) == set(controls.STEP_CONTROLS + controls.MODEL_CONTROLS)
    assert seen.pop("none") is True
    # a decay rounded to bf16 moves the tiny model's loss by less than its
    # limit (its decays are small): on the chip's cell it is a reading
    assert isinstance(seen.pop("decay_bf16"), bool)
    assert not any(seen.values()), seen
