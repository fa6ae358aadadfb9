"""The decoder-hybrid-decoder (``GPTConfig.layer_types``: Mamba mixers,
differential attention under a window and in full, a gated memory unit and
cross attention that read other layers' tensors, a tied head) against the
plain reference ``chipbench/sambay_reference.py``: tiny widths, f32, seeded
random weights, on the CPU."""

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
    DiffAttention, GatedMemoryUnit, GPTConfig, HybridSizes,
    MambaMixer, TransformerLM, lambda_init, next_token_loss)
from bluefog_tpu.ops import local_attention, selective_scan  # noqa: E402
from bluefog_tpu.ops.ring_attention import _splash_attention  # noqa: E402
from chipbench import sambay_reference as ref  # noqa: E402

KINDS = ("mamba", "diff_attention_window", "mamba", "diff_attention", "gmu",
         "cross_diff_attention")
FIRST, WINDOW, VOCAB = 14, 5, 96
SIZES = {"kinds": KINDS, "first_layer": FIRST, "head_dim": 8,
         "window": WINDOW, "d_state": 4, "eps": 1e-5}


def config(**over):
    return GPTConfig(**{**dict(
        vocab_size=VOCAB, hidden_size=64, num_layers=6, num_heads=8,
        dtype=jnp.float32, ffn="swiglu", position="none", ffn_width=96,
        norm_eps=1e-5, layer_types=KINDS, tie_head=True,
        hybrid=HybridSizes(d_inner=128, d_state=4, d_conv=4, dt_rank=4,
                           kv_heads=4, window=WINDOW, first_layer=FIRST)),
        **over})


def shaken(params, seed=5, scale=0.05):
    """Every leaf moved off its initial value, so that zero biases, unit
    scales and the lambdas all carry a gradient worth comparing."""
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
    return shaken(model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"])


@pytest.fixture(scope="module")
def reference_grads(params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: ref.loss(SIZES, p, tokens)))


def system_grads(remat, tokens):
    model = TransformerLM(config(remat=remat))
    return jax.jit(jax.value_and_grad(
        lambda p: next_token_loss(model, p, {}, tokens)))


def dense_attention(q, k, v, **mask):
    return local_attention(q, k, v, causal=True, backend="dense", **mask)


# ---- the selective scan ----------------------------------------------------

def _scan_operands(t, channels, states, seed=0):
    x = rand((2, t, channels), seed)
    delta = jax.nn.softplus(rand((2, t, channels), seed + 1) - 2.0)
    a = -jnp.exp(rand((channels, states), seed + 2, 0.5))
    return (x, delta, a, rand((2, t, states), seed + 3),
            rand((2, t, states), seed + 4), rand((channels,), seed + 5))


@pytest.mark.parametrize("t", [37, 32], ids=["ragged", "chunk_boundary"])
@pytest.mark.parametrize("backend,channels", [
    ("chunked", 24), ("pallas_interpret", 256)])
def test_selective_scan_matches_the_recurrence(backend, channels, t):
    """Output and the gradient of every operand, at a length that ends
    inside a chunk and at one that ends on a chunk's edge."""
    args = _scan_operands(t, channels, 4)
    weight = rand((2, t, channels), 9)

    def through(scan):
        return jax.value_and_grad(
            lambda *a: jnp.sum(scan(*a) * weight), argnums=tuple(range(6)))

    got_m = selective_scan(*args, chunk=8, backend=backend)
    want_m = ref.recurrence(*args)
    np.testing.assert_allclose(got_m, want_m, atol=2e-5, rtol=2e-5)
    got = through(lambda *a: selective_scan(*a, chunk=8, backend=backend))(
        *args)
    want = through(ref.recurrence)(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    assert_trees_close(got[1], want[1])


def test_selective_scan_blocks_of_eight_channel_tiles_agree_with_chunked():
    """2,048 channels: two kernel blocks of eight ``(8, 128)`` tiles, the
    blocking the published width runs with."""
    args = _scan_operands(16, 2048, 16)
    weight = rand((2, 16, 2048), 9)
    grads = [jax.grad(lambda *a: jnp.sum(selective_scan(
        *a, chunk=8, backend=backend) * weight), argnums=tuple(range(6)))(
            *args) for backend in ("pallas_interpret", "chunked")]
    assert_trees_close(*grads)


def test_selective_scan_keeps_the_input_dtype_and_computes_in_f32():
    args = _scan_operands(20, 24, 4)
    x16 = args[0].astype(jnp.bfloat16)
    got = selective_scan(x16, *args[1:], chunk=8, backend="chunked")
    assert got.dtype == jnp.bfloat16
    want = ref.recurrence(x16.astype(jnp.float32), *args[1:])
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=1e-2,
                               atol=1e-2)


@pytest.mark.parametrize("bad", ["backend", "channels", "shapes"])
def test_selective_scan_refuses_what_it_cannot_run(bad):
    args = _scan_operands(8, 24, 4)
    with pytest.raises(ValueError):
        if bad == "backend":
            selective_scan(*args, backend="cuda")
        elif bad == "channels":       # the kernel tiles channels by 128
            selective_scan(*args, backend="pallas_interpret")
        else:
            selective_scan(args[0], args[1][:, :4], *args[2:])


def test_auto_runs_the_chunked_form_off_the_tpu():
    args = _scan_operands(8, 256, 4)
    np.testing.assert_array_equal(
        selective_scan(*args, backend="auto"),
        selective_scan(*args, backend="chunked"))


def test_scan_counters_feed_the_metrics_when_they_are_on():
    from bluefog_tpu.metrics import registry

    args = _scan_operands(20, 24, 4)
    registry.metrics_stop()
    registry._STOPPED = False
    reg = registry.metrics_start()
    try:
        jax.jit(lambda *a: selective_scan(*a, chunk=8, backend="chunked"))(
            *args)
        jax.effects_barrier()
        snap = reg.snapshot()
        assert snap["bf_ssm_scan_tokens_total"] == 2 * 20
        assert snap["bf_ssm_scan_chunks_total"] == 2 * 3
    finally:
        registry.metrics_stop()
        registry._STOPPED = False


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
        assert snap["bf_attn_window_calls_total"] == 1      # layer 15
        assert snap["bf_attn_full_calls_total"] == 2        # layers 17, 19
        assert snap["bf_ssm_scan_tokens_total"] == 2 * 2 * 20
    finally:
        registry.metrics_stop()
        registry._STOPPED = False


# ---- window and grouped heads ----------------------------------------------

def test_window_mask_is_the_band_of_that_many_keys():
    """Uniform scores: a query's output is the mean of the values it sees,
    which are the ``window`` last ones, itself included."""
    t, w = 12, 5
    q = jnp.zeros((1, t, 1, 8))
    v = jnp.arange(t, dtype=jnp.float32).reshape(1, t, 1, 1)
    out = local_attention(q, q, v, causal=True, window=w)[0, :, 0, 0]
    want = [np.mean(range(max(0, i - w + 1), i + 1)) for i in range(t)]
    np.testing.assert_allclose(out, want, rtol=1e-6)


def test_grouped_heads_read_the_group_their_index_falls_in():
    q, k, v = rand((1, 16, 4, 8), 0), rand((1, 16, 2, 8), 1), rand(
        (1, 16, 1, 12), 2)
    got = local_attention(q, k, v, causal=True)
    want = local_attention(q, jnp.repeat(k, 2, axis=2),
                           jnp.repeat(v, 4, axis=2), causal=True)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("bad", ["window_without_causal", "heads", "window"])
def test_local_attention_refuses_masks_and_groups_it_cannot_apply(bad):
    q, k = rand((1, 16, 4, 8), 0), rand((1, 16, 3, 8), 1)
    with pytest.raises(ValueError):
        if bad == "heads":          # 3 key heads do not divide 4 queries
            local_attention(q, k, k, causal=True)
        elif bad == "window":
            local_attention(q, q, q, causal=True, window=0)
        else:
            local_attention(q, q, q, window=4)


@pytest.mark.parametrize("window", [100, None], ids=["window", "full"])
def test_splash_in_the_interpreter_matches_dense_at_64_128_grouped(window):
    """64-wide queries and keys, 128-wide values, 2 key heads and 1 value
    head for 4 query heads, under the window and in full: values and
    gradients of the kernel the chip runs against the dense path."""
    t = 256
    q, k, v = (rand((1, t, 4, 64), 0), rand((1, t, 2, 64), 1),
               rand((1, t, 1, 128), 2))
    weight = rand((1, t, 4, 128), 3)

    def splash(q, k, v):
        return _splash_attention(
            q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 4, axis=2),
            causal=True, scale=0.125, window=window, interpret=True)

    def dense(q, k, v):
        return local_attention(q, k, v, causal=True, window=window)

    def grads(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2))(q, k, v)

    (got, got_g), (want, want_g) = grads(splash), grads(dense)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    assert_trees_close(got_g, want_g, tol=1e-4)


# ---- each mixer against the reference ---------------------------------------

def _apply(module, p, *args):
    return module.apply({"params": p}, *args)


def _mixer_case(kind, params):
    """``(system, reference)``: functions of (this block's mixer parameters,
    the normed input, what it reads of other layers) -> its output."""
    cfg = config()
    y = rand((2, 20, 64), 11)
    memory = rand((2, 20, 128), 12)
    keys, values = rand((2, 20, 4, 8), 13), rand((2, 20, 2, 16), 14)
    i = KINDS.index(kind)
    layer = FIRST + i
    if kind == "mamba":
        p = params[f"block_{i}"]["mamba"]
        return (p, (y,), lambda p, y: _apply(MambaMixer(cfg), p, y),
                lambda p, y: ref.mamba(p, y, SIZES))
    if kind == "gmu":
        p = params[f"block_{i}"]["gmu"]

        def want(p, y, m):
            return (m * jax.nn.silu(y @ p["in_proj"]["kernel"])
                    ) @ p["out_proj"]["kernel"]
        return (p, (y, memory),
                lambda p, y, m: _apply(GatedMemoryUnit(cfg), p, y, m), want)
    p = params[f"block_{i}"]["attn"]
    if kind == "cross_diff_attention":
        def got(p, y, k, v):
            return _apply(DiffAttention(cfg, layer, cross=True), p, y,
                          dense_attention, (k, v))[0]

        def want(p, y, k, v):
            return ref.cross_diff_attention(
                p, y, k, v.reshape(2, 20, 4, 8), layer, SIZES)
        return p, (y, keys, values), got, want
    window = WINDOW if kind == "diff_attention_window" else None

    def got(p, y):
        out, (k, v) = _apply(DiffAttention(cfg, layer, window=window), p, y,
                             dense_attention)
        return out, k, v.reshape(k.shape)
    return (p, (y,), got,
            lambda p, y: jax.tree_util.tree_leaves(
                ref.diff_attention(p, y, layer, window, SIZES)))


@pytest.mark.parametrize("kind", sorted(set(KINDS)))
def test_each_mixer_matches_the_reference_in_value_and_gradient(kind, params):
    """What the mixer returns (its output and what it hands on) and the
    gradient of a weighted sum of it, with respect to its parameters, its
    input and what it reads of other layers."""
    p, inputs, got, want = _mixer_case(kind, params)
    outs = jax.tree_util.tree_leaves(jax.jit(want)(p, *inputs))
    weights = [rand(o.shape, 20 + j) for j, o in enumerate(outs)]

    def scalar(fn):
        return lambda *a: sum(
            jnp.sum(o * w) for o, w in zip(
                jax.tree_util.tree_leaves(fn(*a)), weights))

    argnums = tuple(range(1 + len(inputs)))
    assert_trees_close(jax.tree_util.tree_leaves(jax.jit(got)(p, *inputs)),
                       outs)
    assert_trees_close(
        jax.jit(jax.grad(scalar(got), argnums))(p, *inputs),
        jax.jit(jax.grad(scalar(want), argnums))(p, *inputs), tol=5e-5)


@pytest.mark.parametrize("layer,want", [(0, 0.2), (15, 0.79333), (17, 0.79634),
                                        (19, 0.79799)])
def test_lambda_init_follows_the_published_layer_index(layer, want):
    assert lambda_init(layer) == pytest.approx(want, abs=1e-5)
    assert float(ref.lambda_init(layer)) == pytest.approx(want, abs=1e-5)


# ---- the whole model --------------------------------------------------------

@pytest.mark.parametrize("remat", [False, True], ids=["stored", "remat"])
def test_model_loss_and_gradients_match_the_reference(
        remat, params, tokens, reference_grads):
    """The shared memory and keys/values cross ``nn.remat`` as block outputs
    and inputs: the gradients are the reference's either way."""
    got = system_grads(remat, tokens)(params)
    want = reference_grads(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    assert_trees_close(got[1], want[1], tol=5e-5)


@pytest.mark.parametrize("reader,source", [(4, 2), (5, 3)],
                         ids=["gmu_reads_mamba", "cross_reads_full"])
def test_readers_send_their_gradient_back_to_the_layer_they_read(
        reader, source, params, tokens, reference_grads):
    """Silence the reader's output projection: the source block's gradient
    changes by what flowed back through the shared tensor (and still equals
    the reference's)."""
    system = system_grads(True, tokens)
    name = "gmu" if reader == 4 else "attn"
    out = "out_proj" if reader == 4 else "out"
    silenced = jax.tree_util.tree_map(lambda a: a, params)
    silenced[f"block_{reader}"][name][out] = jax.tree_util.tree_map(
        jnp.zeros_like, params[f"block_{reader}"][name][out])

    def source_grad(p, grads):
        return grads(p)[1][f"block_{source}"]

    full, cut = source_grad(params, system), source_grad(silenced, system)
    moved = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(full), jax.tree_util.tree_leaves(cut)))
    assert moved > 1e-4
    assert_trees_close(cut, source_grad(silenced, reference_grads),
                       tol=5e-5)


def test_tied_leaf_gradient_is_the_sum_of_its_two_uses(params, tokens):
    assert "lm_head" not in params
    got = system_grads(False, tokens)(params)[1]["tok"]["embedding"]

    def two_leaves(table, head):
        p = {**params, "tok": {"embedding": table}}
        x = ref.trunk(SIZES, p, tokens[:, :-1])
        return ref.tied_cross_entropy(x, params["ln_f"], head,
                                      tokens[:, 1:], SIZES["eps"])

    as_table, as_head = jax.jit(jax.grad(two_leaves, argnums=(0, 1)))(
        params["tok"]["embedding"], params["tok"]["embedding"])
    assert float(jnp.max(jnp.abs(as_table))) > 0
    assert float(jnp.max(jnp.abs(as_head))) > 0
    assert_trees_close(got, as_table + as_head)


def test_existing_kinds_keep_their_separate_head_and_their_tree():
    variables = TransformerLM(GPTConfig.tiny()).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert "lm_head" in variables["params"]
    assert set(variables["params"]["block_0"]) == {
        "ln1", "ln2", "qkv", "proj", "up", "down"}


def test_mamba_initialisers_are_the_published_ones():
    p = MambaMixer(config()).init(jax.random.PRNGKey(3),
                                  jnp.zeros((1, 8, 64)))["params"]
    np.testing.assert_allclose(
        p["A_log"], jnp.broadcast_to(jnp.log(jnp.arange(1.0, 5.0)),
                                     (128, 4)), rtol=1e-6)
    np.testing.assert_array_equal(p["D"], jnp.ones(128))
    delta = jax.nn.softplus(p["dt_proj"]["bias"])
    assert 1e-3 * 0.999 <= float(delta.min()) and float(
        delta.max()) <= 0.1 * 1.001
    assert float(jnp.abs(p["dt_proj"]["kernel"]).max()) <= 4 ** -0.5


SCOPES = ("bf.ssm.project", "bf.ssm.conv", "bf.ssm.scan", "bf.gmu.gate",
          "bf.attn.diff", "bf.attn.project")


def test_scopes_the_benchmark_reads_reach_the_compiled_step_unnested(
        params, tokens):
    model = TransformerLM(config(remat=True))
    text = jax.jit(jax.grad(lambda p: next_token_loss(
        model, p, {}, tokens))).lower(params).compile().as_text()
    seen = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        for one_op in op_name.split(";"):
            found = re.findall(r"bf\.[a-z]+\.[a-z]+", one_op)
            assert len(found) <= 1, one_op          # leaf-level, never nested
            seen.update(found)
    assert set(SCOPES) <= seen, set(SCOPES) - seen
    rules = json.load(open(os.path.join(
        REPO, "chipbench", "phases", "step_ssm.json")))["rules"]
    by_phase = {phase: re.compile(pattern) for phase, _, pattern in rules}
    for scope in ("bf.ssm.project", "bf.ssm.conv", "bf.gmu.gate"):
        assert by_phase["ssm_mix"].search(scope)
        assert not by_phase["ssm_scan"].search(scope)
    assert by_phase["ssm_scan"].search("bf.ssm.scan")


# ---- what GPTConfig refuses -------------------------------------------------

@pytest.mark.parametrize("over", [
    dict(layer_types=KINDS[:5]),                         # one kind short
    dict(layer_types=KINDS[:5] + ("linear_attention",)),
    dict(layer_types=("gmu",) + KINDS[1:]),              # no memory yet
    dict(layer_types=("mamba", "diff_attention_window", "mamba",
                      "diff_attention_window", "gmu",
                      "cross_diff_attention")),          # no full k, v yet
    dict(hybrid=None), dict(position="learned"), dict(ffn="gelu"),
    dict(norm="rmsnorm"), dict(mtp_depth=1), dict(num_heads=2),
    dict(hybrid=HybridSizes(kv_heads=3)),
], ids=["count", "unknown", "gmu_first", "cross_without_full", "no_sizes",
        "positions", "gelu", "rmsnorm", "mtp", "heads_fewer_than_kv",
        "odd_kv_heads"])
def test_config_refuses_layer_types_it_cannot_build(over):
    with pytest.raises(ValueError):
        config(**over)


@pytest.mark.parametrize("over", [
    dict(position="none"), dict(hybrid=HybridSizes())])
def test_config_refuses_hybrid_pieces_without_layer_types(over):
    with pytest.raises(ValueError):
        GPTConfig(**over)


# ---- the benchmark's configuration ------------------------------------------

@pytest.fixture(scope="module")
def published():
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    config, traffic = cells.open_cell(manifest, "phi4flash.t8192.solo")
    family = manifest.module("families", config["family"]).build(
        config, traffic)
    return manifest, config, family


def test_configuration_file_counts_697_million_parameters(published):
    _, cfg_file, family = published
    shapes = jax.eval_shape(family.init, jax.random.PRNGKey(0))[0]
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == cfg_file["parameters"] == 697_094_272
    block = {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
             for k, v in shapes["block_0"].items()}
    assert block["mamba"] == 41_241_600 and block["mlp"] == 78_643_200
    assert sum(x.size for x in jax.tree_util.tree_leaves(
        shapes["block_1"]["attn"])) == 19_668_864
    assert sum(x.size for x in jax.tree_util.tree_leaves(
        shapes["block_5"]["attn"])) == 13_112_704
    assert shapes["tok"]["embedding"].shape == (25_008, 2_560)


def test_configuration_keeps_layers_14_to_19_one_of_each_kind(published):
    _, cfg_file, family = published
    cfg = family.model.cfg
    assert cfg.layer_types == KINDS
    assert cfg.hybrid == HybridSizes(
        d_inner=5120, d_state=16, d_conv=4, dt_rank=160, kv_heads=20,
        window=512, first_layer=14)
    assert (cfg.hidden_size, cfg.ffn_width, cfg.num_heads, cfg.norm_eps) == (
        2560, 10240, 40, 1e-5)
    assert cfg.tie_head and cfg.remat and cfg.position == "none"
    assert cfg_file["reduced"] == ["num_hidden_layers", "vocab_size"]


def test_layer_kinds_of_the_whole_published_model(published):
    manifest, _, _ = published
    kind = manifest.module("families", "sambay").layer_kind
    kinds = [kind(layer, 32, 2) for layer in range(32)]
    assert [kinds.count(k) for k in (
        "mamba", "diff_attention_window", "diff_attention", "gmu",
        "cross_diff_attention")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "diff_attention"
    assert kinds[18] == "gmu" and kinds[31] == "cross_diff_attention"


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", False), ("hidden_act", "gelu"),
    ("mb_per_layer", 4), ("mlp_bias", True)])
def test_family_refuses_what_it_does_not_compute(published, key, value):
    manifest, cfg_file, _ = published
    build = manifest.module("families", "sambay").build
    with pytest.raises(SystemExit):
        build({**cfg_file, key: value}, {"seq_len": 64, "batch": 1,
                                         "remat": True})


@pytest.mark.duration_budget(90)   # compiles init, step, the reference's
# step and the two model-loss evaluations, as test_latent_moe's twin
def test_the_family_runs_through_the_harness_and_agrees(tmp_path):
    """``cell.build_cell`` and three steps of ``run.py::agreement`` on a
    virtual CPU device, from a manifest written here and a tiny configuration
    that exists only under ``tests/data``."""
    from chipbench import cell as cells
    from chipbench import run

    data = os.path.join(REPO, "tests", "data", "sambay")
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "t40.b2.remat.solo.json").write_text(json.dumps({
        "ranks": 1, "batch": 2, "seq_len": 40, "remat": True,
        "comm": "neighbor", "topology": "ExponentialTwoGraph",
        "backend": "auto"}))
    manifest_path = tmp_path / "BENCHMARK.json"
    manifest_path.write_text(json.dumps({
        "paths": [str(tmp_path), "chipbench"],
        "configs": [{"name": "tiny-sambay",
                     "file": os.path.join(data, "tiny-sambay.json")}],
        "workloads": [{"name": "tinysambay.solo", "config": "tiny-sambay",
                       "traffic": "t40.b2.remat.solo", "chips": 1}]}))
    manifest = cells.Manifest.load(str(manifest_path))
    cell = cells.build_cell(manifest, "tinysambay.solo", seed=2147483659)
    assert cell.family.model.cfg.layer_types == KINDS
    state, cell.state = cell.state, None
    for k in range(2):                                   # as the warm-up
        state, loss = cell.step(state, cell.ring[k])
    report = {}
    ok, leaves, loss_err = run.agreement(cell, state, 2, report)
    assert ok, (leaves[:3], loss_err, report)
    assert loss_err < 1e-4
    assert report["model_loss"]["rel_err"] < 1e-4
    assert report["model_loss"]["reference"] > 1.0       # ln(250) = 5.5
