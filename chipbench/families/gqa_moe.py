"""Family ``gqa_moe``: a SmallThinker-style decoder (arXiv:2507.20984)
through the repo's one trunk (``bluefog_tpu.models.TransformerLM`` with
``attention="grouped_query"``, ``layer_types`` of un-positioned full layers
and rotary window layers, ``ffn="routed+shared"`` with a softmax top-k router that
reads the block's input, ReGLU experts, no shared expert and no dense block,
RMSNorm, an untied head) at the widths the configuration file gives, holding
a contiguous run of the published layers, this chip's share of the routed
experts and its slice of the vocabulary; next-token cross entropy on seeded
random tokens.  Brings ``reference_loss``: the plain model of
``chipbench/gqa_moe_reference.py``."""

import dataclasses
import math

import jax
import jax.numpy as jnp

try:
    from bluefog_tpu.models.transformer import GroupedSizes
except ImportError:
    raise SystemExit(
        "chipbench: family gqa_moe needs a program whose TransformerLM "
        "builds attention='grouped_query' (bluefog_tpu.models.transformer."
        "GroupedSizes); this checkout has none") from None
from bluefog_tpu.models.transformer import (
    ExpertSizes, GPTConfig, TransformerLM, next_token_loss)

from chipbench import gqa_moe_flops, gqa_moe_reference, latent_moe_flops

# what the family computes; a configuration that asks for anything else
# is refused, not approximated
FIXED = {"moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
         "rope_scaling": None, "tie_word_embeddings": False}
# (rope_layout, sliding_window_layout) of a layer -> its type
KINDS = {(0, 0): "full_attention", (1, 1): "window_rotary_attention"}


@dataclasses.dataclass(frozen=True)
class GqaMoe:
    model: TransformerLM
    embedding_std: float
    batch: int
    seq_len: int
    item = "tokens"

    @property
    def items_per_step(self) -> int:
        return self.batch * self.seq_len

    def init(self, key):
        # the shapes of the parameters do not depend on the length; the
        # softmax router keeps no buffer
        tokens = jnp.zeros((1, 16), jnp.int32)
        params = dict(self.model.init(key, tokens)["params"])
        # flax draws the table at hidden ** -0.5 an element; the
        # configuration states the element's deviation (its `assumed` says
        # why: what the routers of random weights see)
        table = params["tok"]["embedding"]
        params["tok"] = {"embedding": table * (
            self.embedding_std * math.sqrt(table.shape[1]))}
        return params, {}

    def make_batch(self, key):
        return jax.random.randint(
            key, (self.batch, self.seq_len + 1), 0,
            self.model.cfg.vocab_size, dtype=jnp.int32)

    def loss(self, params, model_state, batch):
        return next_token_loss(self.model, params, model_state,
                               batch), model_state

    def reference_loss(self, params, model_state, batch):
        cfg = self.model.cfg
        return gqa_moe_reference.loss(
            {"kinds": cfg.layer_types, "head_dim": cfg.grouped.head_dim,
             "window": cfg.grouped.window,
             "rope_theta": cfg.grouped.rope_theta, "eps": cfg.norm_eps,
             "top_k": cfg.experts.top_k,
             "held_first": cfg.experts.held[0],
             "train_router": cfg.experts.train_router}, params, batch)

    def flops_per_item(self) -> float:
        """Forward + backward of one token; the held experts at the uniform
        expectation of ``top_k * held / router outputs`` assignments a
        token (1.5 for 16 of 64 at top-6)."""
        cfg = self.model.cfg
        gq, ex = cfg.grouped, cfg.experts
        return gqa_moe_flops.train_flops_per_token(
            kinds=cfg.layer_types, hidden=cfg.hidden_size,
            heads=cfg.num_heads, kv_heads=gq.kv_heads, head_dim=gq.head_dim,
            seq_len=self.seq_len, window=gq.window,
            router_outputs=ex.num_experts, top_k=ex.top_k,
            experts_held=ex.held[1], expert_width=ex.width,
            vocab_rows=cfg.vocab_size)

    def kernel_costs(self) -> dict:
        """Per step and chip, by the name a metric's ``params`` asks for."""
        cfg = self.model.cfg
        gq, ex = cfg.grouped, cfg.experts
        calls = 2 if cfg.remat else 1
        itemsize = jnp.dtype(cfg.dtype).itemsize
        expected_rows = (self.items_per_step * ex.top_k * ex.held[1]
                         / ex.num_experts)
        return {
            "gqa_attention": gqa_moe_flops.gqa_attention_cost(
                self.batch, cfg.num_heads, gq.kv_heads, self.seq_len,
                gq.head_dim,
                windows=gqa_moe_flops.windows_of(cfg.layer_types, gq.window),
                forward_calls=calls, itemsize=itemsize),
            "grouped_matmul": latent_moe_flops.grouped_matmul_cost(
                expected_rows, cfg.hidden_size, ex.width,
                layers=cfg.num_layers, forward_calls=calls,
                itemsize=itemsize, experts_held=ex.held[1])}


def build(config: dict, traffic: dict) -> GqaMoe:
    for key, value in FIXED.items():
        if config[key] != value:
            raise SystemExit(f"chipbench: family gqa_moe computes "
                             f"{key}={value!r}, the configuration asks for "
                             f"{config[key]!r}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise SystemExit(
            f"chipbench: seq_len {traffic['seq_len']} exceeds the "
            f"configuration's {config['max_position_embeddings']} positions")
    deployment = config["deployment"]
    first = deployment["first_layer"]
    layers = range(first, first + config["num_hidden_layers"])
    try:
        kinds = tuple(KINDS[config["rope_layout"][i],
                            config["sliding_window_layout"][i]]
                      for i in layers)
    except KeyError as e:
        raise SystemExit(
            f"chipbench: family gqa_moe computes un-positioned full layers "
            f"and rotary window layers {sorted(KINDS)}; the configuration "
            f"has a layer of (rope, window) = {e.args[0]}") from None
    cfg = GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        max_position=config["max_position_embeddings"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(traffic["remat"]), attention="grouped_query",
        ffn="routed+shared", norm="rmsnorm", position="none",
        norm_eps=config["rms_norm_eps"], layer_types=kinds,
        grouped=GroupedSizes(
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            window=config["sliding_window_size"],
            rope_theta=float(config["rope_theta"])),
        experts=ExpertSizes(
            num_experts=deployment["router_outputs"],
            top_k=config["moe_num_active_primary_experts"],
            width=config["moe_ffn_hidden_size"], num_shared=0, scale=1.0,
            held=(deployment["experts_held_first"],
                  config["moe_num_primary_experts"]),
            first_dense=0, router="softmax_topk", activation="relu",
            router_input="block",
            train_router=deployment["router_trains"]))
    return GqaMoe(TransformerLM(cfg), config["embedding_init_std"],
                  traffic["batch"], traffic["seq_len"])
