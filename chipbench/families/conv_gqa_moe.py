"""Family ``conv_gqa_moe``: an LFM2-style decoder (the language model of
LiquidAI/LFM2-8B-A1B) through the repo's one trunk
(``bluefog_tpu.models.TransformerLM`` with ``attention="grouped_query"``,
``layer_types`` of ``short_conv`` layers and full rotary layers with a
per-head RMSNorm on queries and keys, ``ffn="routed+shared"`` with a sigmoid
top-k router that has a selection bias and LFM2's ``1e-6`` in its normaliser,
SwiGLU experts, no shared expert, leading dense blocks, RMSNorm, a tied head)
at the widths the configuration file gives, holding a contiguous run of the
published layers, this chip's share of the routed experts and its slice of
the vocabulary; next-token cross entropy on seeded random tokens.  Brings
``reference_loss``: the plain model of
``chipbench/conv_gqa_moe_reference.py``."""

import dataclasses

import jax
import jax.numpy as jnp

try:
    from bluefog_tpu.models.transformer import ShortConvSizes
except ImportError:
    raise SystemExit(
        "chipbench: family conv_gqa_moe needs a program whose TransformerLM "
        "builds 'short_conv' layers (bluefog_tpu.models.transformer."
        "ShortConvSizes); this checkout has none") from None
from bluefog_tpu.models.transformer import (
    ExpertSizes, GPTConfig, GroupedSizes, TransformerLM, next_token_loss)

from chipbench import (
    conv_gqa_moe_flops, conv_gqa_moe_reference, gqa_moe_flops,
    latent_moe_flops)

# what the family computes; a configuration that asks for anything else
# is refused, not approximated
FIXED = {"model_type": "lfm2_moe", "conv_bias": False, "norm_topk_prob": True,
         "use_expert_bias": True, "tie_word_embeddings": True}
# the source's layer type -> the trunk's
KINDS = {"conv": "short_conv", "full_attention": "full_rotary_attention"}
WEIGHT_EPS = 1e-6     # of the router's normaliser, in the modelling code


@dataclasses.dataclass(frozen=True)
class ConvGqaMoe:
    model: TransformerLM
    kinds: tuple          # the source's names of the layers held
    batch: int
    seq_len: int
    item = "tokens"

    @property
    def items_per_step(self) -> int:
        return self.batch * self.seq_len

    def init(self, key):
        # the shapes of the parameters do not depend on the length
        tokens = jnp.zeros((1, 16), jnp.int32)
        variables = self.model.init(key, tokens)
        return variables["params"], {"buffers": variables["buffers"]}

    def make_batch(self, key):
        return jax.random.randint(
            key, (self.batch, self.seq_len + 1), 0,
            self.model.cfg.vocab_size, dtype=jnp.int32)

    def loss(self, params, model_state, batch):
        return next_token_loss(self.model, params, model_state,
                               batch), model_state

    def reference_loss(self, params, model_state, batch):
        cfg = self.model.cfg
        gq, ex = cfg.grouped, cfg.experts
        return conv_gqa_moe_reference.loss(
            {"kinds": self.kinds, "head_dim": gq.head_dim,
             "rope_theta": gq.rope_theta, "eps": cfg.norm_eps,
             "dense_blocks": ex.first_dense, "top_k": ex.top_k,
             "scale": ex.scale, "weight_eps": ex.weight_eps,
             "held_first": ex.held[0], "train_router": ex.train_router},
            params, model_state, batch)

    def flops_per_item(self) -> float:
        """Forward + backward of one token; the held experts at the uniform
        expectation of ``top_k * held / router outputs`` assignments a
        token (1 for 8 of 32 at top-4)."""
        cfg = self.model.cfg
        gq, ex = cfg.grouped, cfg.experts
        return conv_gqa_moe_flops.train_flops_per_token(
            kinds=self.kinds, hidden=cfg.hidden_size, heads=cfg.num_heads,
            kv_heads=gq.kv_heads, head_dim=gq.head_dim, seq_len=self.seq_len,
            dense_blocks=ex.first_dense, dense_width=cfg.ffn_width,
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
        attention_layers = self.kinds.count(conv_gqa_moe_flops.ATTENTION)
        return {
            "attention": gqa_moe_flops.gqa_attention_cost(
                self.batch, cfg.num_heads, gq.kv_heads, self.seq_len,
                gq.head_dim, windows=[None] * attention_layers,
                forward_calls=calls, itemsize=itemsize),
            "grouped_matmul": latent_moe_flops.grouped_matmul_cost(
                expected_rows, cfg.hidden_size, ex.width,
                layers=cfg.num_layers - ex.first_dense, forward_calls=calls,
                itemsize=itemsize, experts_held=ex.held[1]),
            "gate_conv": conv_gqa_moe_flops.gate_conv_cost(
                self.items_per_step, cfg.hidden_size,
                layers=self.kinds.count(conv_gqa_moe_flops.CONV),
                taps=cfg.short_conv.taps, forward_calls=calls,
                itemsize=itemsize)}


def build(config: dict, traffic: dict) -> ConvGqaMoe:
    for key, value in FIXED.items():
        if config[key] != value:
            raise SystemExit(f"chipbench: family conv_gqa_moe computes "
                             f"{key}={value!r}, the configuration asks for "
                             f"{config[key]!r}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise SystemExit(
            f"chipbench: seq_len {traffic['seq_len']} exceeds the "
            f"configuration's {config['max_position_embeddings']} positions")
    deployment = config["deployment"]
    published = deployment["published"]
    first, layers = deployment["first_layer"], config["num_hidden_layers"]
    kinds = tuple(config["layer_types"])
    if list(kinds) != published["layer_types"][first:first + layers]:
        raise SystemExit(
            "chipbench: layer_types is the published list's entries "
            f"{first}..{first + layers - 1} (deployment.first_layer on)")
    if max(published["num_dense_layers"] - first, 0) != (
            config["num_dense_layers"]):
        raise SystemExit("chipbench: num_dense_layers counts the dense "
                         "layers from deployment.first_layer on")
    unknown = set(kinds) - set(KINDS)
    if unknown:
        raise SystemExit(f"chipbench: family conv_gqa_moe computes the "
                         f"layers {sorted(KINDS)}; the configuration has "
                         f"{sorted(unknown)}")
    cfg = GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=layers, num_heads=config["num_attention_heads"],
        max_position=config["max_position_embeddings"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(traffic["remat"]), attention="grouped_query",
        ffn="routed+shared", norm="rmsnorm", position="none",
        ffn_width=config["intermediate_size"], norm_eps=config["norm_eps"],
        tie_head=True, layer_types=tuple(KINDS[kind] for kind in kinds),
        grouped=GroupedSizes(
            kv_heads=config["num_key_value_heads"],
            head_dim=config["hidden_size"] // config["num_attention_heads"],
            window=config["max_position_embeddings"],   # no layer has one
            rope_theta=float(config["rope_theta"]), qk_norm=True),
        short_conv=ShortConvSizes(taps=config["conv_L_cache"]),
        experts=ExpertSizes(
            num_experts=deployment["router_outputs"],
            top_k=config["num_experts_per_tok"],
            width=config["moe_intermediate_size"], num_shared=0,
            scale=float(config["routed_scaling_factor"]),
            held=(deployment["experts_held_first"], config["num_experts"]),
            first_dense=config["num_dense_layers"],
            train_router=bool(deployment["router_trains"]),
            weight_eps=WEIGHT_EPS))
    return ConvGqaMoe(TransformerLM(cfg), kinds, traffic["batch"],
                      traffic["seq_len"])
