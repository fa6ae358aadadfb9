"""A looped language model on the one decoder path (``GPTConfig.rounds``,
``.sandwich_norm``, ``.exit_gate``; Ouro, arXiv:2510.25741) against the plain
reference ``chipbench/looped_decoder_reference.py``: tiny widths, f32, seeded
random weights, on the CPU (the weighted head its loss stands on is held to
whole-logits arithmetic in ``tests/test_head_loss.py``)."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bluefog_tpu.metrics import registry  # noqa: E402
from bluefog_tpu.models.transformer import (  # noqa: E402
    ExpertSizes, GPTConfig, GroupedSizes, HybridSizes, TransformerLM,
    exit_distribution, next_token_loss)
from chipbench import looped_decoder_reference as ref  # noqa: E402

DATA = os.path.join(REPO, "tests", "data", "looped_decoder")
R, L, VOCAB, BETA = 4, 2, 96, 0.1
SIZES = {"rounds": R, "head_dim": 16, "rope_theta": 1e6, "eps": 1e-6,
         "beta": BETA}


def config(**over):
    return GPTConfig(**{**dict(
        vocab_size=VOCAB, hidden_size=64, num_layers=L, num_heads=4,
        dtype=jnp.float32, attention="grouped_query", ffn="swiglu",
        norm="rmsnorm", position="none", ffn_width=96, norm_eps=1e-6,
        layer_types=("full_rotary_attention",) * L,
        grouped=GroupedSizes(kv_heads=4, head_dim=16, window=0,
                             rope_theta=1e6),
        rounds=R, sandwich_norm=True, exit_gate=True), **over})


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(3), (2, 25), 0, VOCAB)


@pytest.fixture(scope="module")
def params(tokens):
    """Seeded weights with every scale, the gate and its bias away from
    their initial 1 and 0, so that no term of the loss is hidden."""
    tree = TransformerLM(config()).init(jax.random.PRNGKey(1),
                                        tokens[:, :24])["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))

    def shaken(path, leaf):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "exit_gate" in name:
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(shaken, tree)


def system_loss(cfg, params, tokens, beta=BETA):
    return next_token_loss(TransformerLM(cfg), params, {}, tokens,
                           exit_entropy_weight=beta)


def highest(f):
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(f)(*args)
    return run


# ---- the model against the plain reference ----------------------------------

@pytest.mark.parametrize("remat", [False, True], ids=["saved", "remat"])
def test_loss_and_every_leaf_s_gradient_match_the_reference(params, tokens,
                                                            remat):
    got = highest(jax.value_and_grad(
        lambda p: system_loss(config(remat=remat), p, tokens)))(params)
    want = highest(jax.value_and_grad(
        lambda p: ref.loss(SIZES, p, tokens)))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)
    assert (jax.tree_util.tree_structure(got[1])
            == jax.tree_util.tree_structure(want[1]))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree_util.tree_leaves(want[1])):
        assert float(jnp.abs(w).max()) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-6 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("exit_", range(R), ids=lambda r: f"exit_{r + 1}")
def test_every_exit_s_logits_and_the_gate_match_the_reference(params, tokens,
                                                              exit_):
    got, gates = highest(lambda p: TransformerLM(config()).apply(
        {"params": p}, tokens[:, :24]))(params)
    want, want_gates = highest(lambda p: ref.logits(
        SIZES, p, tokens[:, :24]))(params)
    assert len(got) == R and gates.shape == (R, 2, 24)
    assert got[exit_].dtype == jnp.float32
    np.testing.assert_allclose(got[exit_], want[exit_], rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(gates[exit_], want_gates[exit_], rtol=1e-4,
                               atol=2e-5)
    # the exits differ: a round does something
    assert float(jnp.abs(want[exit_] - want[exit_ - 1]).max()) > 1e-2


def test_a_shared_leaf_s_gradient_is_the_sum_over_an_unshared_twin_s_copies(
        params, tokens):
    """The twin is the plain model with ``R * L`` blocks of their own
    leaves, round ``r``'s block ``l`` a copy of the shared block ``l``; the
    system's gradient of a trunk leaf is the sum of the twin's ``R``."""
    def twin_loss(copies):
        rounds = iter(range(R))

        def exits(sizes, shared, toks):
            # one round at a time, each over its own copy of the blocks
            positions = jnp.arange(toks.shape[1])
            x = shared["tok"]["embedding"][toks]
            states, gates = [], []
            for r in rounds:
                for i in range(L):
                    x = ref.block(copies[r * L + i], x, positions, sizes)
                x = ref.rms(x, shared["ln_f"]["scale"], sizes["eps"])
                states.append(x)
                gates.append((x @ shared["exit_gate"]["kernel"])[..., 0]
                             + shared["exit_gate"]["bias"][0])
            return states, jnp.stack(gates)

        real, ref.exits = ref.exits, exits
        try:
            return ref.loss(SIZES, params, tokens)
        finally:
            ref.exits = real

    copies = [params[f"block_{i % L}"] for i in range(R * L)]
    twin = highest(jax.grad(twin_loss))(copies)
    got = highest(jax.grad(lambda p: system_loss(config(), p, tokens)))(
        params)
    for i in range(L):
        summed = jax.tree_util.tree_map(
            lambda *g: sum(g), *[twin[r * L + i] for r in range(R)])
        one_round = jax.tree_util.tree_leaves(twin[i])
        for (path, g), w, first in zip(
                jax.tree_util.tree_leaves_with_path(got[f"block_{i}"]),
                jax.tree_util.tree_leaves(summed), one_round):
            scale = float(jnp.abs(w).max())
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-6 * scale,
                                       err_msg=jax.tree_util.keystr(path))
            # and no single round's: the sum is of parts that matter
            assert float(jnp.abs(w - first).max()) > 1e-2 * scale


# ---- the exit distribution and the expected loss ----------------------------

def test_the_exit_distribution_sums_to_one_and_is_the_reference_s():
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (R, 3, 7))
    log_p = exit_distribution(logits)
    p = jnp.exp(log_p)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p, ref.exit_probabilities(logits), rtol=1e-5,
                               atol=1e-7)
    g = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(p[1], g[1] * (1 - g[0]), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(p[-1], jnp.prod(1 - g[:-1], axis=0),
                               rtol=1e-5, atol=1e-7)
    # the last round's own gate is not read
    moved = exit_distribution(logits.at[-1].add(5.0))
    np.testing.assert_array_equal(moved, log_p)


@pytest.mark.parametrize("bias,exit_", [(40.0, 0), (-40.0, R - 1)],
                         ids=["leaves_at_once", "stays_to_the_end"])
def test_the_loss_tends_to_one_exit_s_as_the_gate_s_bias_grows(params, tokens,
                                                               bias, exit_):
    """``b_g -> +inf``: every token leaves after round 1 and the loss is
    exit 1's cross entropy; ``-> -inf``: exit R's.  The entropy goes to 0,
    and nothing is ``nan`` on the way."""
    pinned = {**params, "exit_gate": {
        "kernel": params["exit_gate"]["kernel"],
        "bias": jnp.full((1,), bias)}}
    loss, grads = highest(jax.value_and_grad(
        lambda p: system_loss(config(), p, tokens)))(pinned)
    logits, _ = highest(lambda p: ref.logits(SIZES, p, tokens[:, :-1]))(
        pinned)
    want = -jnp.take_along_axis(jax.nn.log_softmax(logits[exit_]),
                                tokens[:, 1:, None], axis=-1).mean()
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))


@pytest.mark.parametrize("control", [
    "no_fourth_round", "no_post_norms", "beta_0", "uniform_p",
    "interleaved_rotary"])
def test_the_reference_tells_each_wrong_model_apart(params, tokens, control):
    """What the cell's controls change in the plain model, one place each,
    moves its loss by more than the system differs from it."""
    sound = float(highest(lambda p: ref.loss(SIZES, p, tokens))(params))
    got = float(highest(lambda p: system_loss(config(), p, tokens))(params))
    assert abs(got - sound) < 1e-5 * sound
    sizes, saved = dict(SIZES), (ref.after, ref.exit_probabilities,
                                 ref.rotary)
    try:
        if control == "no_fourth_round":
            sizes["rounds"] = R - 1
        elif control == "no_post_norms":
            ref.after = lambda y, scale, eps: y
        elif control == "beta_0":
            sizes["beta"] = 0.0
        elif control == "uniform_p":
            ref.exit_probabilities = lambda g: jnp.full_like(g, 1.0 / len(g))
        else:       # pairs (2i, 2i + 1) turned where the source pairs halves
            ref.rotary = lambda x, positions, theta: saved[2](
                x.reshape(x.shape[:-1] + (-1, 2)).swapaxes(-1, -2).reshape(
                    x.shape), positions, theta)
        wrong = float(highest(lambda p: ref.loss(sizes, p, tokens))(params))
    finally:
        ref.after, ref.exit_probabilities, ref.rotary = saved
    assert abs(wrong - sound) > 1e-3 * sound, (control, wrong, sound)


# ---- the structure: what the fields add, and what they leave ----------------

def test_the_tree_holds_one_copy_of_every_leaf_whatever_the_rounds(params):
    assert set(params) == {"tok", "block_0", "block_1", "ln_f", "lm_head",
                           "exit_gate"}
    assert set(params["block_0"]) == {"ln1", "ln1_post", "attn", "ln2",
                                      "ln2_post", "mlp"}
    assert params["exit_gate"]["kernel"].shape == (64, 1)
    assert params["exit_gate"]["bias"].shape == (1,)
    once = jax.eval_shape(lambda: TransformerLM(config(
        rounds=1, exit_gate=False)).init(jax.random.PRNGKey(0),
                                         jnp.zeros((1, 8), jnp.int32)))
    again = {k: v for k, v in params.items() if k != "exit_gate"}
    assert (jax.tree_util.tree_structure(once["params"])
            == jax.tree_util.tree_structure(again))


TINY = {"latent_moe": "tiny-latent-moe", "sambay": "tiny-sambay",
        "gqa_moe": "tiny-gqa-moe", "linear_latent_moe": "tiny-ling",
        "conv_gqa_moe": "tiny-lfm2", "mamba2_gqa_moe": "tiny-nemotron"}


def older_model(preset):
    """The preset's model and its ``init(key) -> (params, model_state)``."""
    if preset == "gpt-tiny":
        model = TransformerLM(GPTConfig.tiny())
        return model, lambda key: (model.init(key, jnp.zeros(
            (1, 16), jnp.int32))["params"], {})
    from chipbench import cell as cells
    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    family_name, = (f for f, p in TINY.items() if p == preset)
    config = cells.load_json(os.path.join(
        REPO, "tests", "data", family_name, preset + ".json"))
    family = manifest.module("families", family_name).build(
        config, {"seq_len": 32, "batch": 2, "remat": True})
    return family.model, family.init


@pytest.mark.parametrize("preset", [*TINY.values(), "gpt-tiny"])
def test_at_the_defaults_an_older_model_has_the_tree_and_the_program_it_had(
        preset):
    """One pass, no sandwich, no gate is what every older family's tiny
    preset builds: no leaf of the loop in its tree, and the loss traced
    with the three fields spelled out is the loss traced without them."""
    model, init = older_model(preset)
    cfg = model.cfg
    assert (cfg.rounds, cfg.sandwich_norm, cfg.exit_gate) == (1, False, False)
    tokens = jnp.zeros((2, 33 + cfg.mtp_depth), jnp.int32)
    tree, state = jax.eval_shape(init, jax.random.PRNGKey(0))
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(tree)]
    assert not [p for p in paths if "_post" in p or "exit_gate" in p]
    spelled = TransformerLM(dataclasses.replace(
        cfg, rounds=1, sandwich_norm=False, exit_gate=False), mlp=model.mlp)

    def program(m):
        return str(jax.make_jaxpr(lambda p, s: next_token_loss(
            m, p, s, tokens))(tree, state))
    assert program(spelled) == program(model)


def test_the_defaults_are_one_pass_no_sandwich_no_gate():
    cfg = GPTConfig()
    assert (cfg.rounds, cfg.sandwich_norm, cfg.exit_gate) == (1, False, False)
    assert cfg == GPTConfig(rounds=1, sandwich_norm=False, exit_gate=False)


@pytest.mark.parametrize("over,message", [
    (dict(rounds=0), "one pass of the stack or more"),
    (dict(rounds=1), "several exits"),
    (dict(exit_gate=False), "several exits"),
    (dict(mtp_depth=1, layer_types=None, attention="fused_qkv", grouped=None,
          position="learned"), "no MTP module"),
    (dict(ffn="routed+shared", experts=ExpertSizes(
        num_experts=8, top_k=2, width=16, held=(0, 8), first_dense=0)),
     "selection-bias buffer"),
    (dict(attention="fused_qkv", grouped=None, norm="layernorm",
          layer_types=("mamba", "diff_attention"), hybrid=HybridSizes(
              d_inner=128, d_state=4, d_conv=4, dt_rank=4, kv_heads=4,
              window=5)), "SambaY mixers"),
], ids=["no_rounds", "gate_without_rounds", "rounds_without_gate", "mtp",
        "sigmoid_router", "carried"])
def test_config_refuses_what_a_round_does_not_carry(over, message):
    with pytest.raises(ValueError, match=message):
        config(**over)


def test_a_softmax_router_loops(tokens):
    """What a round can carry is not refused: routed experts without a
    buffer run every round on the same leaves."""
    cfg = config(ffn="routed+shared", ffn_width=None, experts=ExpertSizes(
        num_experts=8, top_k=2, width=16, num_shared=0, scale=1.0,
        held=(0, 8), first_dense=0, router="softmax_topk"))
    model = TransformerLM(cfg)
    tree = model.init(jax.random.PRNGKey(0), tokens[:, :24])["params"]
    assert "moe" in tree["block_0"] and "ln2_post" in tree["block_0"]
    loss = jax.jit(lambda p: next_token_loss(model, p, {}, tokens))(tree)
    assert bool(jnp.isfinite(loss))


# ---- spans and counters -----------------------------------------------------

def scan_lengths(jaxpr):
    """The ``length`` of every ``scan`` in ``jaxpr``, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn.params["length"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += scan_lengths(sub)
    return found


def test_a_pass_shows_four_rounds_and_four_exits(params, tokens):
    """One loop of ``R`` rounds a pass (forward, and backward again) whose
    body, blocks and final norm, lies under ``bf.loop.round``; ``exit_<r>``
    around each exit's head call; ``bf.loop.exit`` on the gate and the
    expected loss; a block's four norms under ``bf.block.norm``; no op under
    two layer scopes."""
    import re

    step = jax.value_and_grad(
        lambda p: system_loss(config(remat=True), p, tokens))
    assert scan_lengths(jax.make_jaxpr(step)(params).jaxpr) == [R, R]
    text = jax.jit(step).lower(params).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    assert any("bf.loop.round" in n and "block_1" in n for n in names)
    for r in range(1, R + 1):
        # JAX writes a stack's first scope as ``jvp(<scope>)``
        for scope in ("bf.head.logits", "bf.head.loss"):
            assert any(re.search(rf"exit_{r}\)?/{scope}/", n)
                       for n in names), (r, scope)
    assert not any(f"exit_{R + 1}" in n for n in names)
    assert any("bf.loop.exit" in n and "exit_gate" in n for n in names)
    for norm in ("ln1", "ln1_post", "ln2", "ln2_post", "ln_f"):
        assert any(f"bf.block.norm/{norm}/" in n for n in names), norm
    layer = re.compile(
        r"bf\.(?:embed|block|attn|mla|mlp|moe|ssm|gmu|head)\.\w+|bf\.loop\.exit")
    for n in names:
        assert len(set(layer.findall(n))) <= 1, n


@pytest.mark.parametrize("remat", [False, True], ids=["saved", "remat"])
def test_counters_of_the_loop(params, tokens, remat):
    """With metrics on the step traces, forward and backward, with and
    without rematerialised blocks (the loop's body carries no callback),
    and reads the loop's counters once a pass."""
    registry.metrics_stop()
    reg = registry.metrics_start()
    try:
        loss, _ = jax.jit(jax.value_and_grad(lambda p: system_loss(
            config(remat=remat), p, tokens)))(params)
        jax.block_until_ready(loss)
        jax.effects_barrier()
        snap = reg.snapshot()
    finally:
        registry.metrics_stop()
    assert snap["bf_loop_rounds"] == R
    assert snap["bf_loop_block_calls_total"] == R * L
    for r in range(1, R + 1):
        assert snap[f'bf_head_loss_chunks{{site="exit_{r}"}}'] == 1
    mass = [snap[f'bf_loop_exit_mass{{round="{r}"}}'] for r in range(1, R + 1)]
    _, gates = highest(lambda p: ref.logits(SIZES, p, tokens[:, :-1]))(params)
    want = ref.exit_probabilities(gates).mean(axis=(1, 2))
    np.testing.assert_allclose(mass, want, rtol=1e-4)
    np.testing.assert_allclose(sum(mass), 1.0, rtol=1e-5)
    np.testing.assert_allclose(
        snap["bf_loop_expected_rounds"],
        float(jnp.sum(want * jnp.arange(1, R + 1))), rtol=1e-4)
    # a program traced with metrics off carries nothing of them
    text = jax.jit(lambda p: system_loss(config(), p, tokens)).lower(
        params).as_text()
    assert "callback" not in text


# ---- the configuration file and the family ----------------------------------

CELL = "ouro.t4096.solo"
# the catalog row's `config` (model-configs guide, architectures.jsonl), as
# read from ByteDance/Ouro-2.6B's config.json; layer_types is 48 equal entries
CATALOG = {'early_exit_threshold': 1,
 'head_dim': 128,
 'hidden_act': 'silu',
 'hidden_size': 2048,
 'intermediate_size': 5632,
 'max_position_embeddings': 65536,
 'max_window_layers': 48,
 'model_type': 'ouro',
 'num_attention_heads': 16,
 'num_hidden_layers': 48,
 'num_key_value_heads': 16,
 'rms_norm_eps': 1e-06,
 'rope_scaling': None,
 'rope_theta': 1000000,
 'sliding_window': None,
 'tie_word_embeddings': False,
 'total_ut_steps': 4,
 'use_sliding_window': False,
 'vocab_size': 49152}
CATALOG["layer_types"] = ["full_attention"] * 48
REDUCED = ["num_hidden_layers"]


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
    """No width is in ``reduced``, nor the number of rounds: depth alone."""
    cfg_file = published[1]
    if key in REDUCED:
        assert cfg_file[key] != CATALOG[key]
        assert cfg_file["deployment"]["published"][key] == CATALOG[key]
    else:
        assert cfg_file[key] == CATALOG[key]
        assert type(cfg_file[key]) is type(CATALOG[key])


def test_the_configuration_file_states_its_cuts_and_its_deployment(published):
    manifest, cfg_file, traffic, family = published
    assert cfg_file["reduced"] == REDUCED == list(cfg_file["changed"])
    deployment = cfg_file["deployment"]
    assert (cfg_file["num_hidden_layers"], cfg_file["total_ut_steps"]) == (
        8, 4)
    assert deployment["chips_sharing_a_layer"] == 1
    assert deployment["first_layer"] == 0
    assert "what_the_cut_distorts" in deployment
    for key in ("exit_entropy_weight", "next_round_input", "exit_gate",
                "sandwich_norm", "rotary_pairing", "early_exit_threshold",
                "sequence_length", "optimizer", "initialisers",
                "compute_dtype"):
        assert key in cfg_file["assumed"], key
    assert "half-split" in cfg_file["assumed"]["rotary_pairing"]
    assert cfg_file["exit_entropy_weight"] == 0.1
    entry = manifest.entry("configs", "ouro-2.6b")
    assert entry["source"] == cfg_file["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    assert entry["reduced"] == REDUCED
    assert (traffic["seq_len"], traffic["batch"], traffic["remat"],
            traffic["ranks"]) == (4096, 1, True, 1)
    for name in ("model_loss_rtol", "loss_rtol", "rtol", "atol", "reason"):
        assert name in cfg_file["tolerance"], name
    cfg = family.model.cfg
    assert cfg.grouped == GroupedSizes(kv_heads=16, head_dim=128, window=0,
                                       rope_theta=1e6)
    assert (cfg.hidden_size, cfg.num_heads, cfg.ffn_width, cfg.norm_eps) == (
        2048, 16, 5632, 1e-6)
    assert (cfg.ffn, cfg.norm, cfg.position) == ("swiglu", "rmsnorm", "none")
    assert cfg.remat and not cfg.tie_head and cfg.dtype == jnp.bfloat16


def test_the_parameter_count_is_the_files_and_the_issue_s(published):
    _, cfg_file, _, family = published
    shapes, state = jax.eval_shape(family.init, jax.random.PRNGKey(0))
    assert state == {}
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))
    block = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert count == cfg_file["parameters"] == (
        8 * block + 2 * 49152 * 2048 + 2048 + 2049) == 612_438_017
    assert sorted(shapes) == sorted(
        [f"block_{i}" for i in range(8)]
        + ["tok", "lm_head", "ln_f", "exit_gate"])
    assert sum(x.size for x in jax.tree_util.tree_leaves(
        shapes["block_3"])) == block
    assert shapes["lm_head"]["kernel"].shape == (2048, 49152)
    # the uncut model by the same parts: the published 2.6B
    assert 2.6e9 < 48 * block + 2 * 49152 * 2048 + 4097 < 2.7e9


@pytest.mark.parametrize("key,value", [
    ("model_type", "llama"), ("hidden_act", "gelu"),
    ("tie_word_embeddings", True), ("use_sliding_window", True),
    ("sliding_window", 4096), ("rope_scaling", {"type": "yarn"}),
    ("layer_types", ["sliding_attention"] * 48)])
def test_family_refuses_what_it_does_not_compute(published, key, value):
    manifest, cfg_file, _, _ = published
    build = manifest.module("families", "looped_decoder").build
    with pytest.raises(SystemExit):
        build({**cfg_file, key: value},
              {"seq_len": 64, "batch": 1, "remat": True})
