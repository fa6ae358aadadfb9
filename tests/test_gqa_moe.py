"""Grouped-query attention in un-positioned full layers and rotary window
layers, and the expert layer stated by ``ExpertSizes`` (a softmax top-k router
that reads the block's input, ReGLU experts, no shared expert, no dense
block), against the plain reference ``chipbench/gqa_moe_reference.py``: tiny
widths, f32, seeded random weights, on the CPU."""

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
    HybridSizes, LatentSizes, RoutedFFN, TransformerLM, next_token_loss,
    rotary)
from bluefog_tpu.ops import local_attention  # noqa: E402
from bluefog_tpu.ops.moe import routed_experts, softmax_topk_router  # noqa: E402
from chipbench import gqa_moe_reference as ref  # noqa: E402

GLOBAL, WINDOWED = "full_attention", "window_rotary_attention"
KINDS = (GLOBAL, WINDOWED, WINDOWED, GLOBAL)
E, K, WINDOW, VOCAB, THETA = 8, 3, 7, 96, 10000.0
SIZES = {"kinds": KINDS, "head_dim": 16, "window": WINDOW,
         "rope_theta": THETA, "eps": 1e-6, "top_k": K, "held_first": 2,
         "train_router": True}


def experts(**over):
    return ExpertSizes(**{**dict(
        num_experts=E, top_k=K, width=32, num_shared=0, scale=1.0,
        held=(2, 4), first_dense=0, router="softmax_topk",
        activation="relu", router_input="block"), **over})


def config(**over):
    return GPTConfig(**{**dict(
        vocab_size=VOCAB, hidden_size=64, num_layers=4, num_heads=6,
        dtype=jnp.float32, attention="grouped_query", ffn="routed+shared",
        norm="rmsnorm", position="none", norm_eps=1e-6, layer_types=KINDS,
        grouped=GroupedSizes(kv_heads=2, head_dim=16, window=WINDOW,
                             rope_theta=THETA),
        experts=experts()), **over})


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
def params(tokens):
    model = TransformerLM(config())
    return shaken(jax.jit(model.init)(jax.random.PRNGKey(0),
                                      tokens[:, :-1])["params"])


def dense_attention(q, k, v, **mask):
    return local_attention(q, k, v, causal=True, backend="dense", **mask)


# ---- rotary, half-split -----------------------------------------------------

@pytest.mark.parametrize("width", [8, 128])
def test_half_split_rotary_equals_the_reference_and_keeps_the_norm(width):
    x = rand((2, 9, 3, width), 2)
    positions = 5 + jnp.arange(9)
    got = rotary(x, positions[None], 1.5e6, interleaved=False)
    np.testing.assert_allclose(got, ref.rotary(x, positions, 1.5e6),
                               atol=2e-5)
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # pair i is (i, i + width / 2): position 0 turns nothing, and the two
    # pairings are different functions of the same head
    np.testing.assert_allclose(
        rotary(x, jnp.zeros((1, 9), jnp.int32), 1.5e6, interleaved=False), x)
    assert not np.allclose(got, rotary(x, positions[None], 1.5e6))


# ---- the router --------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_router_is_a_softmax_over_all_top_k_renormalised(seed):
    x, kernel = rand((50, 32), seed), rand((32, 64), seed + 10)
    idx, weights = softmax_topk_router(x, kernel, top_k=6)
    probs = jax.nn.softmax(x @ kernel, axis=-1)             # over all 64
    top, want_idx = jax.lax.top_k(probs, 6)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(weights, top / top.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    dense = ref.route(kernel, x, 6)
    np.testing.assert_allclose(
        jnp.take_along_axis(dense, idx, axis=-1), weights, rtol=1e-5)
    assert int((dense > 0).sum()) == 50 * 6


# ---- the gate's activation, forward and through the rule of its own --------

def _plain_share(activation, x, idx, weights, wg, wu, wd, first):
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    out = jnp.zeros_like(x)
    for j in range(wg.shape[0]):
        w = jnp.where(idx == first + j, weights, 0.0).sum(-1)
        out = out + w[:, None] * ((act(x @ wg[j]) * (x @ wu[j])) @ wd[j])
    return out


@pytest.mark.parametrize("backend", ["ragged", "gmm_interpret",
                                     "gmm_interpret+scatter"])
@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_gate_activation_forward_and_custom_backward(activation, backend,
                                                     monkeypatch):
    """Both gates through both ways a pass's rows reach their tokens: the
    kernel that sums in VMEM (what the Pallas backends take where the shape
    fits) and XLA's scatter-add (the portable backend's; asked for under
    the interpreter as well)."""
    from bluefog_tpu.ops import moe as moe_ops

    backend, _, scatter = backend.partition("+")
    if scatter:
        monkeypatch.setattr(moe_ops, "_sums_in_vmem", lambda t, d, b: False)
    x, kernel = rand((128, 64), 0), rand((64, E), 1)
    wg, wu, wd = (rand((4, 64, 32), 2, 0.2), rand((4, 64, 32), 3, 0.2),
                  rand((4, 32, 64), 4, 0.2))
    idx, _ = softmax_topk_router(x, kernel, top_k=K)
    probe = rand((128, 64), 5)

    def got(x, weights, wg, wu, wd):
        y, _ = routed_experts(x, idx, weights, wg, wu, wd, num_experts=E,
                              held=(2, 4), backend=backend,
                              activation=activation)
        return jnp.sum(y * probe)

    def want(x, weights, wg, wu, wd):
        return jnp.sum(_plain_share(activation, x, idx, weights, wg, wu, wd,
                                    2) * probe)

    weights = jax.nn.softmax(rand((128, K), 6), axis=-1)
    args = (x, weights, wg, wu, wd)
    g, w = (jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))(*args)
            for f in (got, want))
    np.testing.assert_allclose(g[0], w[0], rtol=2e-5)
    assert_trees_close(g[1], w[1])


def test_relu_and_silu_gates_differ_and_an_unknown_one_is_refused():
    x = rand((16, 64), 0)
    idx = jnp.zeros((16, 1), jnp.int32)
    ws = rand((1, 64, 32), 1), rand((1, 64, 32), 2), rand((1, 32, 64), 3)
    outs = [routed_experts(x, idx, jnp.ones((16, 1)), *ws, num_experts=1,
                           held=(0, 1), activation=a)[0]
            for a in ("relu", "silu")]
    assert not np.allclose(*outs)
    with pytest.raises(ValueError, match="activation"):
        routed_experts(x, idx, jnp.ones((16, 1)), *ws, num_experts=1,
                       held=(0, 1), activation="gelu")


# ---- the expert layer as ExpertSizes states it ------------------------------

def test_no_shared_expert_and_no_dense_block_build_no_shared_and_no_buffers():
    model = TransformerLM(config())
    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    jnp.zeros((1, 8), jnp.int32))
    assert "buffers" not in variables and "params" in variables
    for i in range(4):
        block = variables["params"][f"block_{i}"]
        assert set(block) == {"ln1", "attn", "ln2", "moe"}     # no `mlp`
        assert set(block["moe"]) == {"router", "w_gate", "w_up", "w_down"}
        assert set(block["attn"]) == {"q", "k", "v", "o"}
        assert all(set(leaf) == {"kernel"} for leaf in block["attn"].values())
    assert variables["params"]["block_0"]["moe"]["router"].shape == (64, E)
    assert variables["params"]["block_0"]["attn"]["q"]["kernel"].shape == (
        64, 6 * 16)
    assert variables["params"]["block_0"]["attn"]["k"]["kernel"].shape == (
        64, 2 * 16)


def test_the_sigmoid_router_keeps_its_buffer_and_its_shared_expert():
    cfg = config(experts=experts(router="sigmoid_noaux_tc", num_shared=2,
                                 first_dense=1, activation="silu",
                                 router_input="ffn"), ffn_width=48)
    variables = jax.jit(TransformerLM(cfg).init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert set(variables["params"]["block_0"]) == {"ln1", "attn", "ln2",
                                                   "mlp"}
    assert variables["params"]["block_1"]["moe"]["shared"]["gate"][
        "kernel"].shape == (64, 2 * 32)
    assert set(variables["buffers"]) == {"block_1", "block_2", "block_3"}


@pytest.mark.parametrize("where", ["block", "ffn"])
def test_the_router_reads_the_input_experts_sizes_names(where):
    """One block, its attention replaced by one that returns a constant
    far from zero: the routing changes with the attention's output only
    where the router reads the feed-forward's input."""
    cfg = config(num_layers=1, layer_types=(GLOBAL,),
                 experts=experts(router_input=where, held=(0, E)))
    block = Block(cfg, mixer=GLOBAL)
    x = rand((1, 12, 64), 3)
    positions = jnp.arange(12)[None]
    variables = {"params": block.init(
        jax.random.PRNGKey(0), x, dense_attention, positions)["params"]}

    def rows_per_expert(shift):
        def attn_fn(q, k, v, **mask):
            return dense_attention(q, k, v, **mask) + shift
        _, state = block.apply(variables, x, attn_fn, positions,
                               mutable=["moe_metrics"])
        return np.asarray(state["moe_metrics"]["moe"]["rows_per_expert"][0])

    same = np.array_equal(rows_per_expert(0.0), rows_per_expert(30.0))
    assert same == (where == "block")


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference_layer():
    """64 experts, top-6, four chips of 16: ``held = (0, 16) .. (48, 16)``.
    No shared expert, so nothing is counted twice."""
    ex = experts(num_experts=64, top_k=6, width=8, held=(0, 64))
    whole = RoutedFFN(config(hidden_size=32, experts=ex))
    y, z = rand((2, 16, 32), 5), rand((2, 16, 32), 6)
    params = shaken(whole.init(jax.random.PRNGKey(0), z)["params"], scale=0.2)
    routing = whole.apply({"params": params}, y, method="route")
    uncut = whole.apply({"params": params}, z, routing)
    total = jnp.zeros_like(z)
    for first in range(0, 64, 16):
        share_cfg = config(hidden_size=32, experts=experts(
            num_experts=64, top_k=6, width=8, held=(first, 16)))
        share = {**params, **{name: params[name][first:first + 16]
                              for name in ("w_gate", "w_up", "w_down")}}
        total = total + RoutedFFN(share_cfg).apply(
            {"params": share}, z, routing)
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    want = ref.held_experts(params, z, ref.route(params["router"], y, 6), 0)
    np.testing.assert_allclose(uncut, want, atol=2e-5)


# ---- attention ---------------------------------------------------------------

@pytest.mark.parametrize("kind", [GLOBAL, WINDOWED])
def test_attention_layer_matches_the_reference_in_value_and_gradient(kind):
    cfg = config()
    module = GroupedQueryAttention(cfg, kind)
    y = rand((2, 12, 64), 7)
    positions = jnp.arange(12)
    p = shaken(module.init(jax.random.PRNGKey(0), y, dense_attention,
                           positions[None])["params"])
    probe = rand((2, 12, 64), 8)

    def got(p, y):
        return jnp.sum(probe * module.apply({"params": p}, y,
                                            dense_attention, positions[None]))

    def want(p, y):
        return jnp.sum(probe * ref.gqa(p, y, positions, kind, SIZES))

    g, w = (jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(p, y)
            for f in (got, want))
    np.testing.assert_allclose(g[0], w[0], rtol=2e-5)
    assert_trees_close(g[1], w[1])


def test_query_heads_read_the_group_their_index_falls_in():
    """6 query heads over 2 key/value heads: heads 0-2 read group 0."""
    cfg = config()
    seen = {}

    def attn_fn(q, k, v, **mask):
        seen.update(q=q.shape, k=k.shape, v=v.shape, mask=mask)
        return dense_attention(q, k, v, **mask)

    module = GroupedQueryAttention(cfg, WINDOWED)
    y = rand((1, 10, 64), 1)
    variables = module.init(jax.random.PRNGKey(0), y, attn_fn,
                            jnp.arange(10)[None])
    assert seen == {"q": (1, 10, 6, 16), "k": (1, 10, 2, 16),
                    "v": (1, 10, 2, 16), "mask": {"window": WINDOW}}
    GroupedQueryAttention(cfg, GLOBAL).apply(variables, y, attn_fn,
                                             jnp.arange(10)[None])
    assert seen["mask"] == {}


@pytest.mark.parametrize("kinds,moves", [((GLOBAL,) * 4, False),
                                         (KINDS, True)],
                         ids=["global_only", "with_window_layers"])
def test_only_rotary_layers_see_a_shift_of_the_positions(kinds, moves, tokens):
    """A global layer has no positional encoding: nothing of it moves with
    the positions, bit for bit.  A window layer turns its queries and keys
    by position.  Rotary is relative, so a common ``position_offset`` leaves
    its output where it was up to rounding (the turned q and k differ, their
    products do not); positions stretched by two change what it computes."""
    model = TransformerLM(config(layer_types=kinds))
    params = shaken(jax.jit(model.init)(jax.random.PRNGKey(0),
                                        tokens[:, :-1])["params"])
    t = tokens.shape[1] - 1
    apply = jax.jit(lambda positions: model.apply(
        {"params": params}, tokens[:, :-1], positions=positions))
    base = apply(jnp.arange(t)[None])
    offset = apply(1000 + jnp.arange(t)[None])     # position_offset=1000
    stretched = apply(2 * jnp.arange(t)[None])
    # relative: a common offset moves neither kind (to rounding)
    np.testing.assert_allclose(offset, base, atol=2e-3)
    if moves:
        assert float(jnp.max(jnp.abs(stretched - base))) > 1e-2
    else:
        np.testing.assert_array_equal(stretched, base)
        np.testing.assert_array_equal(offset, base)


def test_attention_counters_tell_window_layers_from_full_ones(params, tokens):
    from bluefog_tpu.metrics import registry

    registry.metrics_stop()
    registry._STOPPED = False
    reg = registry.metrics_start()
    try:
        model = TransformerLM(config())
        jax.jit(lambda p: model.apply({"params": p}, tokens[:, :-1]))(params)
        jax.effects_barrier()
        snap = reg.snapshot()
        assert snap["bf_attn_window_calls_total"] == 2
        assert snap["bf_attn_full_calls_total"] == 2
        assert snap["bf_moe_assignments_total"] == 4 * 2 * 20 * K
        assert 0 < snap["bf_moe_assignments_held_total"] < 4 * 2 * 20 * K
        assert snap["bf_moe_row_passes_total"] >= 4
    finally:
        registry.metrics_stop()
        registry._STOPPED = False


# ---- the whole model ---------------------------------------------------------

@pytest.mark.duration_budget(60)   # the first compile of the plain reference
@pytest.mark.parametrize("train_router", [True, False],
                         ids=["router_trains", "router_held_still"])
@pytest.mark.parametrize("remat", [False, True], ids=["stored", "remat"])
def test_model_loss_and_gradients_match_the_reference(remat, train_router,
                                                      params, tokens):
    """Four layers, two of each kind, the window (7) shorter than the
    sequence (20), the router reading the block's input; with the routing
    weights as constants of the backward pass no router has a gradient, in
    the system and in the reference alike, and the loss is the same."""
    model = TransformerLM(config(
        remat=remat, experts=experts(train_router=train_router)))
    sizes = {**SIZES, "train_router": train_router}
    got = jax.jit(jax.value_and_grad(
        lambda p: next_token_loss(model, p, {}, tokens)))(params)
    want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(sizes, p, tokens)))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)
    np.testing.assert_allclose(want[0], ref.loss(SIZES, params, tokens),
                               rtol=1e-6)
    assert_trees_close(got[1], want[1])
    for i in range(len(KINDS)):
        router = np.asarray(got[1][f"block_{i}"]["moe"]["router"])
        assert bool(np.any(router)) == train_router, i


@pytest.mark.parametrize("control", ["rotary_in_the_global_layer",
                                     "window_left_out", "silu_for_relu",
                                     "router_reads_the_ffn_input"])
def test_the_reference_tells_each_wrong_model_apart(control, params, tokens,
                                                    monkeypatch):
    """The controls the cell's ``model_loss_rtol`` must fail on the chip,
    here in f32: each moves the loss by far more than rounding."""
    want = float(ref.loss(SIZES, params, tokens))
    if control == "rotary_in_the_global_layer":
        monkeypatch.setitem(ref.LAYERS, GLOBAL, (True, False))
    elif control == "window_left_out":
        monkeypatch.setitem(ref.LAYERS, WINDOWED, (True, False))
    elif control == "silu_for_relu":
        monkeypatch.setattr(ref, "reglu",
                            lambda gate, up: jax.nn.silu(gate) * up)
    else:
        def block(p, x, positions, kind, sizes):
            h = x + ref.gqa(p["attn"], ref.rms(x, p["ln1"]["scale"],
                                               sizes["eps"]),
                            positions, kind, sizes)
            z = ref.rms(h, p["ln2"]["scale"], sizes["eps"])
            weights = ref.route(p["moe"]["router"], z, sizes["top_k"])
            return h + ref.held_experts(p["moe"], z, weights,
                                        sizes["held_first"])
        monkeypatch.setattr(ref, "block", block)
    wrong = float(ref.loss(SIZES, params, tokens))
    assert abs(wrong - want) / want > 1e-4, (control, wrong, want)


# ---- scopes ------------------------------------------------------------------

SCOPES = ("bf.attn.project", "bf.attn.rotary", "bf.moe.route",
          "bf.moe.dispatch", "bf.moe.experts", "bf.moe.combine")


def test_scopes_the_benchmark_reads_reach_the_compiled_step_unnested(
        params, tokens):
    model = TransformerLM(config(remat=True))
    text = jax.jit(jax.grad(lambda p: next_token_loss(
        model, p, {}, tokens))).lower(params).compile().as_text()
    seen = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        for one_op in op_name.split(";"):
            found = re.findall(r"bf\.[a-z]+\.[a-z]+", one_op)
            assert len(set(found)) <= 1, one_op     # leaf-level, never nested
            seen.update(found)
    assert set(SCOPES) <= seen, set(SCOPES) - seen
    rules = json.load(open(os.path.join(
        REPO, "chipbench", "phases", "step_gqa.json")))["rules"]
    by_phase = {phase: re.compile(pattern) for phase, _, pattern in rules}
    for scope in ("bf.attn.project", "bf.attn.rotary"):
        assert by_phase["attention_project"].search(scope)
    for scope in ("bf.moe.route", "bf.moe.dispatch", "bf.moe.combine"):
        assert by_phase["expert_dispatch"].search(scope)
        assert not by_phase["attention_project"].search(scope)
    assert by_phase["expert_ffn"].search("bf.moe.experts")
    # the accepted rows keep their order under the new ones
    step = json.load(open(os.path.join(
        REPO, "chipbench", "phases", "step.json")))["rules"]
    assert [r for r in rules if r in step] == step


def test_the_early_router_opens_its_scope_before_the_attention(params,
                                                              tokens):
    """In the order the block is traced: route, then the attention's
    projections, then dispatch (the routing crosses the attention)."""
    model = TransformerLM(config())
    text = jax.jit(lambda p: model.apply(
        {"params": p}, tokens[:, :-1])).lower(params).as_text(debug_info=True)
    block0 = [n for n in re.findall(r'loc\("([^"]*)"', text)
              if "block_0/" in n]

    def first(scope):
        return next(i for i, n in enumerate(block0) if scope in n)

    assert first("bf.moe.route") < first("bf.attn.project") < first(
        "bf.moe.dispatch")


# ---- what GPTConfig refuses --------------------------------------------------

@pytest.mark.parametrize("over", [
    dict(layer_types=KINDS[:3]),                          # one kind short
    dict(layer_types=KINDS[:3] + ("sliding_attention",)),
    dict(layer_types=KINDS[:3] + ("mamba",)),             # the two families
    dict(layer_types=None),                               # needs its types
    dict(grouped=None), dict(attention="fused_qkv"),
    dict(position="learned"), dict(position="rotary"), dict(mtp_depth=1),
    dict(experts=None), dict(ffn="swiglu"), dict(ffn="routed"),
    dict(grouped=GroupedSizes(kv_heads=4, head_dim=16, window=WINDOW,
                              rope_theta=THETA)),         # 6 heads over 4
    dict(hybrid=HybridSizes()),                           # no SambaY mixer
    dict(experts=experts(router="top1")),
    dict(experts=experts(activation="gelu")),
    dict(experts=experts(router_input="attention")),
    dict(experts=experts(num_shared=-1)),
], ids=["count", "unknown", "mixed_families", "no_types", "no_sizes",
        "fused_qkv", "learned", "rotary_everywhere", "mtp", "no_experts",
        "swiglu_with_experts", "unknown_ffn", "heads_do_not_divide",
        "hybrid_sizes",
        "router", "activation", "router_input", "negative_shared"])
def test_config_refuses_what_it_cannot_build(over):
    with pytest.raises(ValueError):
        config(**over)


def test_the_older_kinds_build_as_they_did():
    """SambaY still needs its couplings, latent attention its sizes, and
    ``routed+shared`` still names the expert layer."""
    latent = dict(attention="latent", position="rotary", norm="rmsnorm",
                  latent=LatentSizes(), ffn="routed+shared",
                  experts=ExpertSizes())
    assert GPTConfig(**latent).experts.router == "sigmoid_noaux_tc"
    assert GPTConfig(**latent).experts.train_router
    with pytest.raises(ValueError, match="come together"):
        GPTConfig(layer_types=("mamba",) * 12, position="none",
                  ffn="swiglu")                            # no hybrid sizes
    with pytest.raises(ValueError, match="swiglu"):
        GPTConfig(layer_types=("mamba",) * 12, position="none",
                  hybrid=HybridSizes())                    # gelu
    with pytest.raises(ValueError, match="grouped_query"):
        GPTConfig(layer_types=(GLOBAL,) * 12, position="none")
    dense = GPTConfig(attention="grouped_query", position="none",
                      layer_types=(GLOBAL,) * 12,
                      grouped=GroupedSizes(kv_heads=4, head_dim=64,
                                           window=8, rope_theta=THETA))
    assert dense.ffn == "gelu"          # any feed-forward goes with them


# ---- the benchmark's configuration ------------------------------------------

PUBLISHED = {"head_dim": 128, "hidden_size": 2560,
             "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
             "moe_num_active_primary_experts": 6, "num_attention_heads": 28,
             "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
             "rope_theta": 1500000, "sliding_window_size": 4096,
             "moe_primary_router_apply_softmax": True,
             "norm_topk_prob": True, "tie_word_embeddings": False,
             "rope_scaling": None}


@pytest.fixture(scope="module")
def published():
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    cfg_file, traffic = cells.open_cell(manifest, "smallthinker.t16384.solo")
    family = manifest.module("families", cfg_file["family"]).build(
        cfg_file, traffic)
    return manifest, cfg_file, traffic, family


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_file_keeps_the_published_widths(published, key):
    assert published[1][key] == PUBLISHED[key]


def test_the_configuration_file_states_its_cuts_and_its_deployment(published):
    manifest, cfg_file, traffic, family = published
    assert cfg_file["reduced"] == ["num_hidden_layers",
                                   "moe_num_primary_experts", "vocab_size"]
    assert set(cfg_file["changed"]) == set(cfg_file["reduced"])
    deployment = cfg_file["deployment"]
    assert deployment["chips_sharing_a_layer"] == 4
    assert deployment["router_outputs"] == 64
    assert (deployment["experts_held_first"], deployment["experts_held"],
            cfg_file["moe_num_primary_experts"]) == (0, 16, 16)
    assert deployment["vocabulary_shards"] == 8
    assert deployment["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936}
    assert cfg_file["vocab_size"] * 8 == 151936
    assert cfg_file["rope_layout"] == [0, 1, 1, 1] * 13
    assert cfg_file["sliding_window_layout"] == cfg_file["rope_layout"]
    for key in ("router_input", "router_gradient", "rotary_pairing",
                "auxiliary_loss", "secondary_experts", "initialisers",
                "optimizer", "compute_dtype"):
        assert key in cfg_file["assumed"], key
    entry = manifest.entry("configs", "smallthinker-21b-a3b")
    assert entry["source"] == cfg_file["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    assert entry["reduced"] == cfg_file["reduced"]
    assert traffic["seq_len"] == 16384 and traffic["batch"] == 1
    cfg = family.model.cfg
    assert cfg.layer_types == (GLOBAL, WINDOWED, WINDOWED, WINDOWED)
    assert cfg.grouped == GroupedSizes(kv_heads=4, head_dim=128, window=4096,
                                       rope_theta=1.5e6)
    assert cfg.experts == ExpertSizes(
        num_experts=64, top_k=6, width=768, num_shared=0, scale=1.0,
        held=(0, 16), first_dense=0, router="softmax_topk",
        activation="relu", router_input="block", train_router=False)
    assert deployment["router_trains"] is False
    assert cfg.remat and not cfg.tie_head and cfg.dtype == jnp.bfloat16


def test_the_parameter_count_is_the_files(published):
    _, cfg_file, _, family = published
    shapes = jax.eval_shape(family.init, jax.random.PRNGKey(0))[0]
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == cfg_file["parameters"] == 559_290_880
    block = {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
             for k, v in shapes["block_1"].items()}
    assert block == {"attn": 20_971_520, "moe": 163_840 + 16 * 5_898_240,
                     "ln1": 2_560, "ln2": 2_560}
    assert shapes["tok"]["embedding"].shape == (18_992, 2_560)
    assert shapes["lm_head"]["kernel"].shape == (2_560, 18_992)


def test_the_cell_prices_its_kernels_and_its_tokens(published):
    _, _, _, family = published
    band = 4096 * 16384 - 4096 * 4096 // 2           # 58,720,256 pairs
    full = 16384 * 16384 // 2                        # 134,217,728
    flops, nbytes = family.kernel_costs()["gqa_attention"]
    assert flops == 9 * 2 * 28 * 128 * (full + 3 * band) == 20_023_137_533_952
    q, kv, rows = 28 * 16384 * 128 * 2, 2 * 4 * 16384 * 128 * 2, 28 * 16384 * 4
    assert nbytes == 4 * (2 * (2 * q + kv + rows) + 4 * q + 2 * kv + rows)
    gmm_flops, _ = family.kernel_costs()["grouped_matmul"]
    assert gmm_flops == 4 * 12 * 2 * 24576 * 2560 * 768
    macs = (4 * (20_971_520 + 2560 * 64 + 1.5 * 3 * 2560 * 768)
            + 28 * 256 * (full + 3 * band) // 16384 + 2560 * 18992)
    assert family.flops_per_item() == 6 * macs
    assert family.items_per_step == 16384


@pytest.mark.parametrize("key,value", [
    ("moe_primary_router_apply_softmax", False), ("norm_topk_prob", False),
    ("tie_word_embeddings", True), ("rope_scaling", {"factor": 2}),
    ("rope_layout", [1, 1, 1, 1])])
def test_family_refuses_what_it_does_not_compute(published, key, value):
    manifest, cfg_file, _, _ = published
    build = manifest.module("families", "gqa_moe").build
    with pytest.raises(SystemExit):
        build({**cfg_file, key: value}, {"seq_len": 64, "batch": 1,
                                         "remat": True})


def tiny_manifest(tmp_path):
    """A manifest written here around the tiny configuration that exists
    only under ``tests/data``: one cell, ``tinygqamoe.solo``."""
    data = os.path.join(REPO, "tests", "data", "gqa_moe")
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "t40.b2.remat.solo.json").write_text(json.dumps({
        "ranks": 1, "batch": 2, "seq_len": 40, "remat": True,
        "comm": "neighbor", "topology": "ExponentialTwoGraph",
        "backend": "auto"}))
    manifest_path = tmp_path / "BENCHMARK.json"
    manifest_path.write_text(json.dumps({
        "paths": [str(tmp_path), "chipbench"],
        "configs": [{"name": "tiny-gqa-moe",
                     "file": os.path.join(data, "tiny-gqa-moe.json")}],
        "workloads": [{"name": "tinygqamoe.solo", "config": "tiny-gqa-moe",
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
    cell = cells.build_cell(manifest, "tinygqamoe.solo", seed=2147483659)
    cfg = cell.family.model.cfg
    assert cfg.layer_types == (GLOBAL, WINDOWED, WINDOWED, WINDOWED)
    assert cfg.experts.held == (4, 4) and cfg.experts.num_experts == 8
    state, cell.state = cell.state, None
    for k in range(2):                                   # as the warm-up
        state, loss = cell.step(state, cell.ring[k])
    report = {}
    ok, leaves, loss_err = run.agreement(cell, state, 2, report)
    assert ok, (leaves[:3], loss_err, report)
    assert loss_err < 1e-4
    assert report["model_loss"]["rel_err"] < 1e-4
    assert report["model_loss"]["reference"] > 1.0       # ln(250) = 5.5


@pytest.mark.duration_budget(60)
@pytest.mark.parametrize("train_router", [0, 1])
def test_the_routing_script_reads_the_held_share_a_layer(tmp_path,
                                                         train_router):
    """``benchmarks/gqa_moe_routing.py``, which read the cell's routed load
    over its window on the chip (PERF.md section 6, PR 34), on the tiny
    cell: 4 of 8 experts held, so a share near a half in every layer."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import gqa_moe_routing
    from chipbench import cell as cells

    real = cells.open_cell
    try:
        summary = gqa_moe_routing.main([
            "--manifest", tiny_manifest(tmp_path), "--workload",
            "tinygqamoe.solo", "--seed", "2147483801", "--steps", "4",
            "--every", "2", "--train-router", str(train_router)])
    finally:
        cells.open_cell = real
    assert summary["router_trains"] == bool(train_router)
    for key in ("held_share_first", "held_share_last"):
        assert len(summary[key]) == 4
        assert all(0.3 < share < 0.7 for share in summary[key]), summary
    assert summary["row_passes_max"] == [1, 1, 1, 1]


def test_the_combine_script_runs_both_forms_on_a_tiny_layer(tmp_path):
    """``benchmarks/moe_combine_bench.py``, which read the expert layer's
    sums by token as XLA's scatter-adds and through the kernel on the chip
    at both cells' shapes (PERF.md section 6, PR 35), at its tiny shape:
    both forms run (the kernel in the interpreter), the record says which,
    and a CPU run names itself and gives no device time."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import moe_combine_bench

    out = moe_combine_bench.main(
        ["--shapes", "tiny", "--out", str(tmp_path / "bench.json")])
    assert out["platform"] == "cpu"
    for form in ("scatter", "kernel"):
        entry = out[f"tiny.{form}"]
        assert entry["row_buffer"] == 128 and entry["row_passes"] == 1
        assert 0 < entry["held_rows"] < 128
        assert len(entry["wall_ms"]) == 3 and entry["device_ms"] is None
    with open(tmp_path / "bench.json") as f:
        assert json.load(f) == json.loads(json.dumps(out))


@pytest.mark.duration_budget(90)   # the cell, the reference's step twice
def test_the_controls_script_tells_a_wrong_step_and_a_wrong_model(tmp_path,
                                                                  capsys):
    """``benchmarks/gqa_moe_controls.py``, which read the cell's tolerances'
    sound runs and controls on the chip (PERF.md section 6, PR 34), on the
    tiny cell: the sound run agrees, the reference at 1.25 x the rate and the
    plain model with ``silu`` for ``relu`` do not."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import gqa_moe_controls

    gqa_moe_controls.main([
        "--manifest", tiny_manifest(tmp_path), "--workload",
        "tinygqamoe.solo", "--seeds", "2147483801", "--preroll", "4",
        "--controls", "lr_1.25,silu_for_relu", "--out", str(tmp_path / "out")])
    said = {}
    for line in capsys.readouterr().out.splitlines():
        kind, _, fields = line.partition(" ")
        if kind in ("AGREEMENT", "MODEL_LOSS"):
            fields = json.loads(fields)
            said[fields["control"]] = fields
    assert said["sound"]["ok"] and set(said["sound"]["groups"]) == {
        "embedding", "lm_head", "scale", "router", "experts", "attn"}
    assert not said["lr_1.25"]["ok"] and not said["silu_for_relu"]["ok"]
    # the routers take weight decay alone: 0.25 * lr * wd * |w| a step
    assert said["lr_1.25"]["groups"]["router"]["rel"] < 1e-3 < (
        said["lr_1.25"]["groups"]["attn"]["rel"])
    with open(tmp_path / "out" / "sound.seed2147483801.json") as f:
        assert len(json.load(f)["leaves"]) == 43
