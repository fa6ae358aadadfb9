"""The phase scopes inside the compiled step (``bf.<layer>.<phase>``).

The benchmark reads device time by phase through the HLO's ``op_name``
(``chipbench/reducers/scope_ms.py``), so the scopes are part of what it
measures with: each must reach the compiled text, and no op may sit under
two of them (the phases would not add up).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu.ops import collectives as C
from bluefog_tpu.optim import CommunicationType, decentralized_optimizer
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import ExponentialTwoGraph
from bluefog_tpu.topology.schedule import build_schedule
from tests._util import walk_jaxpr

N = 4
OPTIM = {"bf.optim.base_update", "bf.optim.apply", "bf.optim.as_updates"}
FUSE = {"bf.gossip.fuse", "bf.gossip.split"}
PACK = {"bf.gossip.pack", "bf.gossip.unpack"}
EXCHANGE = {"bf.gossip.exchange"}
SCOPE = re.compile(r"bf\.(?:optim|gossip)\.\w+")
FUSE_THRESHOLD = 3000     # bytes: w1 (4096) ships unfused, the rest fuse


@pytest.fixture(autouse=True)
def lowered_fuse_threshold(monkeypatch):
    monkeypatch.setattr(C, "fuse_apply", functools.partial(
        C.fuse_apply, threshold_bytes=FUSE_THRESHOLD))


def mlp_step(comm, atc):
    """A train step of a tiny MLP over four ranks, and its stacked input."""
    opt = decentralized_optimizer(
        optax.sgd(0.1, momentum=0.9), build_schedule(ExponentialTwoGraph(N)),
        "bf", communication_type=comm, atc=atc)

    def loss(p, x):
        return jnp.mean((jnp.tanh(x @ p["w1"] + p["b1"]) @ p["w2"]
                         + p["b2"]) ** 2)

    def step(p_blk, x_blk):
        p = jax.tree_util.tree_map(lambda t: t[0], p_blk)
        updates, _ = opt.update(jax.grad(loss)(p, x_blk[0]), opt.init(p), p)
        return jax.tree_util.tree_map(lambda t: t[None],
                                      optax.apply_updates(p, updates))

    params = {"w1": jnp.ones((N, 16, 64)), "b1": jnp.ones((N, 64)),
              "w2": jnp.ones((N, 64, 8)), "b2": jnp.ones((N, 8))}
    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    fn = shard_map(step, mesh=mesh, in_specs=(P("bf"), P("bf")),
                   out_specs=P("bf"), check_vma=False)
    return fn, (params, jnp.ones((N, 4, 16)))


@pytest.mark.parametrize("atc", [False, True], ids=["awc", "atc"])
@pytest.mark.parametrize("comm,expected", [
    (CommunicationType.neighbor_allreduce, OPTIM | FUSE | EXCHANGE),
    (CommunicationType.allreduce, OPTIM | FUSE),
    (CommunicationType.empty, OPTIM),
], ids=["neighbor", "allreduce", "empty"])
def test_scopes_reach_the_compiled_step_and_never_nest(comm, expected, atc):
    fn, args = mlp_step(comm, atc)
    text = jax.jit(fn).lower(*args).compile().as_text()
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        # XLA joins the names of ops it merges with ";": each is one op's
        for one_op in op_name.split(";"):
            scopes = SCOPE.findall(one_op)
            assert len(scopes) <= 1, one_op
            found.update(scopes)
    assert found == expected


@pytest.mark.parametrize("op", ["win_put", "win_accumulate"])
def test_pack_and_unpack_scopes_on_the_pallas_path(monkeypatch, op):
    """The one site of ``bf.gossip.pack`` / ``.unpack`` from PR 47 on: the
    window deliver kernel's padding to tiles and the slice back, a kernel a
    leaf in between and under neither (``gossip_pack_ms_per_step`` reads the
    two scopes; no cell runs a window op, so it reads 0.0)."""
    from bluefog_tpu.ops import windows as W

    monkeypatch.setenv("BLUEFOG_TPU_PALLAS_INTERPRET", "1")
    sched = build_schedule(ExponentialTwoGraph(N))
    tree = {"w": jnp.ones((N, 16, 64)), "b": jnp.ones((N, 64))}

    def step(blk):
        x = jax.tree_util.tree_map(lambda t: t[0], blk)
        state = W.win_create(x, sched, "bf", name=f"scopes_{op}")
        state = getattr(W, op)(state, x, "bf", backend="pallas")
        return jax.tree_util.tree_map(lambda t: t[None],
                                      W.win_update(state, "bf")[0])

    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    fn = shard_map(step, mesh=mesh, in_specs=(P("bf"),), out_specs=P("bf"),
                   check_vma=False)
    equations = list(walk_jaxpr(jax.make_jaxpr(fn)(tree).jaxpr))
    under = {scope: {eqn.primitive.name for eqn, stack in equations
                     if scope in stack} for scope in PACK}
    assert "pad" in under["bf.gossip.pack"]
    assert "slice" in under["bf.gossip.unpack"]
    assert not any("pallas_call" in names for names in under.values())
    assert not any("bf.gossip.exchange" in stack for _, stack in equations)
    assert sum(eqn.primitive.name == "pallas_call"
               for eqn, _ in equations) == len(tree)


MOE_SCOPE = re.compile(r"bf\.moe\.\w+")


@pytest.mark.parametrize("backend", ["ragged", "gmm_interpret"])
def test_expert_layer_scopes_in_both_rules_and_never_nested(backend):
    """``routed_experts`` differentiates by a rule of its own, whose backward
    runs the passes again: it opens ``bf.moe.dispatch`` / ``.experts`` /
    ``.combine`` itself, in the loop's body.
    ``expert_dispatch_ms_per_step`` reads the first and the last: all three
    reach both rules, and no op sits under two."""
    from bluefog_tpu.ops.moe import routed_experts

    x = jnp.ones((64, 32))
    idx = jnp.tile(jnp.arange(4, dtype=jnp.int32), (64, 1))
    w = (jnp.ones((4, 32, 16)), jnp.ones((4, 32, 16)), jnp.ones((4, 16, 32)))

    def loss(x, weights, *w):
        return jnp.sum(routed_experts(x, idx, weights, *w, num_experts=16,
                                      held=(0, 4), backend=backend)[0] ** 2)

    text = jax.jit(jax.value_and_grad(loss, argnums=range(5))).lower(
        x, jnp.ones((64, 4)), *w).as_text(debug_info=True)
    forward, backward = set(), set()
    for name in re.findall(r'loc\("([^"]*)"', text):
        scopes = set(MOE_SCOPE.findall(name))
        assert len(scopes) <= 1, name
        (backward if "transpose(" in name else forward).update(scopes)
    want = {"bf.moe.dispatch", "bf.moe.experts", "bf.moe.combine"}
    assert forward == backward == want


# ---- the layer scopes of the decoder step -----------------------------------

LAYER_SCOPE = re.compile(
    r"bf\.(?:embed|block|attn|mla|mlp|moe|ssm|gmu|head)\.\w+")
# instructions that do a layer's work; what XLA fuses keeps its op_name
# inside the fused computation, so the whole text is read
HEAVY = re.compile(
    r" (dot|convolution|gather|scatter|reduce|custom-call)\(")
PHASE_OF = {
    "bf.embed.lookup": "embed", "bf.embed.mtp_merge": "embed",
    "bf.block.norm": "norm", "bf.mlp.dense": "mlp",
    "bf.attn.kernel": "attention_wrap",
    "bf.attn.window_kernel": "attention_wrap",
    "bf.attn.project": "attention_project",
    "bf.attn.rotary": "attention_project",
    "bf.attn.diff": "attention_project",
    "bf.mla.project": "attention_project",
    "bf.head.logits": "head_loss", "bf.head.loss": "head_loss",
    "bf.moe.route": "expert_dispatch", "bf.moe.dispatch": "expert_dispatch",
    "bf.moe.combine": "expert_dispatch", "bf.moe.experts": "expert_ffn",
    "bf.ssm.scan": "ssm_scan", "bf.ssm.project": "ssm_mix",
    "bf.ssm.conv": "ssm_mix", "bf.gmu.gate": "ssm_mix"}
TRUNK = {"bf.embed.lookup", "bf.block.norm", "bf.attn.kernel",
         "bf.head.logits", "bf.head.loss"}
MOE = {"bf.moe.route", "bf.moe.dispatch", "bf.moe.experts", "bf.moe.combine"}
# scopes with no op in the backward pass: the routers' selection, and the
# head, whose gradients ops/head_loss.py makes beside the loss in the
# forward pass (the backward pass scales them by a cotangent XLA folds away)
FORWARD_ONLY = {"bf.moe.route", "bf.head.logits", "bf.head.loss"}


def family_config(family):
    """A tiny configuration of each family on the one decoder path, and the
    layer scopes its step opens."""
    from bluefog_tpu.models.transformer import (
        ExpertSizes, GPTConfig, GroupedSizes, HybridSizes, LatentSizes)

    tiny = dict(vocab_size=96, hidden_size=64, dtype=jnp.float32)
    if family == "fused_qkv":
        return dict(tiny, num_layers=2, num_heads=4, max_position=64), (
            TRUNK | {"bf.attn.project", "bf.mlp.dense"})
    if family == "latent_moe":
        return dict(
            tiny, num_layers=2, num_heads=4, attention="latent",
            ffn="routed+shared", norm="rmsnorm", position="rotary",
            ffn_width=96, mtp_depth=1,
            latent=LatentSizes(q_lora_rank=48, kv_lora_rank=32,
                               qk_nope_head_dim=16, qk_rope_head_dim=8,
                               v_head_dim=16),
            experts=ExpertSizes(num_experts=16, top_k=4, width=32,
                                held=(4, 8), first_dense=1)), (
            TRUNK | MOE | {"bf.mla.project", "bf.mlp.dense",
                           "bf.embed.mtp_merge"})
    if family == "sambay":
        return dict(
            tiny, num_layers=6, num_heads=8, ffn="swiglu", position="none",
            ffn_width=96, norm_eps=1e-5, tie_head=True,
            layer_types=("mamba", "diff_attention_window", "mamba",
                         "diff_attention", "gmu", "cross_diff_attention"),
            hybrid=HybridSizes(d_inner=128, d_state=4, d_conv=4, dt_rank=4,
                               kv_heads=4, window=5, first_layer=14)), (
            TRUNK | {"bf.attn.window_kernel", "bf.attn.project",
                     "bf.attn.diff", "bf.mlp.dense", "bf.ssm.project",
                     "bf.ssm.conv", "bf.ssm.scan", "bf.gmu.gate"})
    return dict(
        tiny, num_layers=2, num_heads=6, attention="grouped_query",
        ffn="routed+shared", norm="rmsnorm", position="none",
        layer_types=("full_attention", "window_rotary_attention"),
        grouped=GroupedSizes(kv_heads=2, head_dim=16, window=7,
                             rope_theta=1e4),
        experts=ExpertSizes(num_experts=8, top_k=3, width=32, num_shared=0,
                            scale=1.0, held=(2, 4), first_dense=0,
                            router="softmax_topk", activation="relu",
                            router_input="block")), (
        TRUNK | MOE | {"bf.attn.window_kernel", "bf.attn.project",
                       "bf.attn.rotary"})


@functools.lru_cache(maxsize=None)
def compiled_loss_and_gradient(family, remat, lookup_form="scatter"):
    """The compiled text of ``next_token_loss`` and its gradient, the
    lookup's gradient in the form a CPU takes or in the one asked for."""
    from bluefog_tpu.models.transformer import (
        GPTConfig, TransformerLM, next_token_loss)
    from bluefog_tpu.ops import row_sums

    sizes, opens = family_config(family)
    cfg = GPTConfig(remat=remat, **sizes)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((2, 16 + 1 + cfg.mtp_depth), jnp.int32)
    # shapes alone: an eager init costs more than the compile
    state = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), tokens[:, :16],
        **({"next_tokens": tokens[:, 1:17]} if cfg.mtp_depth else {})))
    params = state.pop("params")

    def loss(params, state):
        return next_token_loss(model, params, state, tokens, mtp_weight=0.1)

    chosen = row_sums._lookup_form
    row_sums._lookup_form = lambda v, d: lookup_form
    try:
        return jax.jit(jax.value_and_grad(loss)).lower(
            params, state).compile().as_text(), opens
    finally:
        row_sums._lookup_form = chosen


def pass_of(op_name):
    if "rematted_computation" in op_name:
        return "recompute"
    return "backward" if "transpose(" in op_name else "forward"


FAMILIES = pytest.mark.parametrize(
    "family", ["fused_qkv", "latent_moe", "sambay", "gqa_moe"])
REMAT = pytest.mark.parametrize("remat", [False, True],
                                ids=["saved", "remat"])


def scopes_by_pass(text):
    """Every layer scope the text names, by pass; (a) and (b) below."""
    found = {"forward": set(), "backward": set(), "recompute": set()}
    for line in text.splitlines():
        named = re.search(r'op_name="([^"]*)"', line)
        if named is None:
            continue
        for one_op in named.group(1).split(";"):
            scopes = set(LAYER_SCOPE.findall(one_op))
            assert len(scopes) <= 1, one_op
            found[pass_of(one_op)] |= scopes
        if HEAVY.search(line):
            assert LAYER_SCOPE.search(named.group(1)), line.strip()[:400]
    return found


@REMAT
@FAMILIES
def test_every_heavy_op_of_the_decoder_step_is_under_one_layer_scope(
        family, remat):
    """(a) Every dot, convolution, gather, scatter, reduction and custom
    call traced under ``TransformerLM`` or ``next_token_loss`` carries a
    layer scope, in the forward, the backward and the recomputed pass alike:
    a module that lands without one fails here.  (b) No op carries two (the
    layers would not add up); ``bf.neighbor_allreduce.slot{k}`` inside
    ``bf.gossip.exchange`` is a Perfetto aid outside this pattern."""
    text, opens = compiled_loss_and_gradient(family, remat)
    found = scopes_by_pass(text)
    assert found["forward"] | found["backward"] | found["recompute"] == opens
    assert found["backward"] >= opens - FORWARD_ONLY
    assert bool(found["recompute"]) == remat


@REMAT
@FAMILIES
def test_the_lookup_s_own_gradient_rule_stays_under_the_embedding_s_scopes(
        family, remat):
    """The step with the table's gradient summed by the kernel
    (``ops/row_sums.py::take_rows``, in the interpreter here): (a) and (b)
    hold as they do for ``jnp.take``'s transpose: what the rule traces
    carries the scope of the call, the kernel ``bf.embed.lookup`` (the MTP
    model's second lookup ``bf.embed.mtp_merge``) in the backward pass; and
    no scatter is left under either scope but the learned positions'."""
    text, opens = compiled_loss_and_gradient(family, remat, "vmem_interpret")
    found = scopes_by_pass(text)
    assert found["backward"] >= opens - FORWARD_ONLY
    for scope in {"bf.embed.lookup"} | (opens & {"bf.embed.mtp_merge"}):
        assert f"))/{scope}/bf_embed_add_rows_by_id/" in text, scope
    scatters = [line for line in text.splitlines()
                if re.search(r" scatter\(", line) and "bf.embed." in line]
    assert all("/pos/" in line for line in scatters), scatters
    assert len(scatters) == (family == "fused_qkv")


@REMAT
@FAMILIES
def test_every_layer_scope_falls_to_its_phase_of_the_layer_table(family,
                                                                 remat):
    """(c) Read as the benchmark reads it (``chipbench/reducers/scope_ms.py``
    with ``phases/step_layers.json``): an instruction under a layer scope
    falls to the phase PHASE_OF names, and none to ``other``."""
    import sys

    from tests._util import REPO
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from chipbench.reducers import scope_ms

    text, opens = compiled_loss_and_gradient(family, remat)
    program = scope_ms.Program(text, scope_ms.load_rules("step_layers"))
    phases = {}
    for name in program.lines:
        # an op XLA merged from two layers' ops carries both names and goes
        # to the earlier row: not a question this test asks
        scopes = set(LAYER_SCOPE.findall(program.op_name(name) or ""))
        if len(scopes) == 1:
            phases.setdefault(scopes.pop(), set()).add(program.phase(name))
    assert phases == {scope: {PHASE_OF[scope]} for scope in opens}
