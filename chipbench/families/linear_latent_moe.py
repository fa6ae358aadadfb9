"""Family ``linear_latent_moe``: a linear-attention / latent-attention hybrid
with routed experts (the language model of Ling-3.0-flash: Kimi Delta
Attention, arXiv:2510.26692, five layers in six beside a latent-attention
layer; sigmoid routing limited to the best groups of experts) through the
repo's one trunk (``bluefog_tpu.models.TransformerLM`` with ``layer_types``
of ``kda`` and ``latent_attention``, ``ffn="routed+shared"``, RMSNorm) at the
widths the configuration file gives, holding a contiguous run of the
published layers and this chip's share of the heads, of the routed experts
and of the vocabulary; next-token cross entropy on seeded random tokens.
Brings ``reference_loss``: the plain model of
``chipbench/linear_latent_moe_reference.py``."""

import dataclasses

import jax
import jax.numpy as jnp

from bluefog_tpu.models.transformer import (
    ExpertSizes, GPTConfig, KdaSizes, LatentSizes, TransformerLM,
    next_token_loss)

from chipbench import kda_flops, latent_moe_flops, linear_latent_moe_reference

# what the family computes; a configuration that asks for anything else
# is refused, not approximated
FIXED = {"q_lora_rank": None, "use_qk_norm": True, "score_function": "sigmoid",
         "moe_router_enable_expert_bias": True, "norm_topk_prob": True,
         "gated_attention_proj_granularity_type": "head_wise",
         "group_norm_size": 1, "linear_silu": True, "kda_safe_gate": True,
         "no_kda_lora": True, "use_kda_lora": False, "use_mla_nope": False,
         "use_nGPT": False, "scale_router_input": False, "value_norm": False,
         "up_proj_norm": False, "num_kv_heads_for_linear_attn": 0}


def layer_kind(layer: int, layer_group_size: int) -> str:
    """The mixer of published layer ``layer`` (from 0): the last of every
    ``layer_group_size`` layers attends, the others are linear."""
    return ("latent_attention" if (layer + 1) % layer_group_size == 0
            else "kda")


@dataclasses.dataclass(frozen=True)
class LinearLatentMoE:
    model: TransformerLM
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
        la, ex = cfg.latent, cfg.experts
        return linear_latent_moe_reference.loss(
            {"kinds": cfg.layer_types, "head_dim": cfg.kda.head_dim,
             "lower_bound": cfg.kda.lower_bound,
             "qk_nope": la.qk_nope_head_dim, "qk_rope": la.qk_rope_head_dim,
             "rope_theta": la.rope_theta, "eps": cfg.norm_eps,
             "top_k": ex.top_k, "scale": ex.scale, "n_group": ex.n_group,
             "topk_group": ex.topk_group, "held_first": ex.held[0]},
            params, model_state, batch)

    def flops_per_item(self) -> float:
        """Forward + backward of one token over the heads and experts this
        chip holds; the held experts at the uniform expectation of ``top_k *
        held / router outputs`` assignments a token (0.125 for 8 of 512 at
        top-8); the delta rule as the recurrence (``kda_flops``)."""
        cfg = self.model.cfg
        la, ex = cfg.latent, cfg.experts
        return kda_flops.train_flops_per_token(
            kinds=cfg.layer_types, hidden=cfg.hidden_size,
            heads=cfg.heads_held[1], kda_dim=cfg.kda.head_dim,
            kv_rank=la.kv_lora_rank, nope=la.qk_nope_head_dim,
            rope=la.qk_rope_head_dim, v_dim=la.v_head_dim,
            seq_len=self.seq_len, dense_blocks=ex.first_dense,
            dense_width=cfg.ffn_width, expert_width=ex.width,
            shared_experts=ex.num_shared, router_outputs=ex.num_experts,
            top_k=ex.top_k, experts_held=ex.held[1],
            vocab_rows=cfg.vocab_size)

    def kernel_costs(self) -> dict:
        """Per step and chip, by the name a metric's ``params`` asks for."""
        cfg = self.model.cfg
        la, ex, heads = cfg.latent, cfg.experts, cfg.heads_held[1]
        calls = 2 if cfg.remat else 1
        itemsize = jnp.dtype(cfg.dtype).itemsize
        expected_rows = (self.items_per_step * ex.top_k * ex.held[1]
                         / ex.num_experts)
        return {
            "kda": kda_flops.kda_cost(
                self.batch, self.seq_len, heads, cfg.kda.head_dim,
                cfg.kda.head_dim, layers=cfg.layer_types.count("kda"),
                forward_calls=calls, itemsize=itemsize),
            "mla_attention": latent_moe_flops.mla_attention_cost(
                self.batch, heads, self.seq_len,
                la.qk_nope_head_dim + la.qk_rope_head_dim, la.v_head_dim,
                layers=cfg.layer_types.count("latent_attention"),
                forward_calls=calls, itemsize=itemsize),
            "grouped_matmul": latent_moe_flops.grouped_matmul_cost(
                expected_rows, cfg.hidden_size, ex.width,
                layers=cfg.num_layers - ex.first_dense, forward_calls=calls,
                itemsize=itemsize, experts_held=ex.held[1])}


def build(config: dict, traffic: dict) -> LinearLatentMoE:
    for key, value in FIXED.items():
        if config[key] != value:
            raise SystemExit(f"chipbench: family linear_latent_moe computes "
                             f"{key}={value!r}, the configuration asks for "
                             f"{config[key]!r}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise SystemExit(
            f"chipbench: seq_len {traffic['seq_len']} exceeds the "
            f"configuration's {config['max_position_embeddings']} positions")
    deployment = config["deployment"]
    published = deployment["published"]
    first = deployment["first_layer"]
    layers = range(first, first + config["num_hidden_layers"])
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(config[key][layer] for layer in layers):
            raise SystemExit(f"chipbench: family linear_latent_moe computes "
                             f"no swiglu limit; {key} has one in the "
                             f"published layers {first}..{layers[-1]}")
    if published["first_k_dense_replace"] - first != (
            config["first_k_dense_replace"]):
        raise SystemExit("chipbench: first_k_dense_replace counts the dense "
                         "layers from deployment.first_layer on")
    cfg = GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=published["num_attention_heads"],
        heads_held=(deployment["heads_held_first"],
                    config["num_attention_heads"]),
        max_position=config["max_position_embeddings"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(traffic["remat"]), attention="latent",
        ffn="routed+shared", norm="rmsnorm", position="rotary",
        ffn_width=config["intermediate_size"],
        norm_eps=config["rms_norm_eps"],
        layer_types=tuple(layer_kind(l, config["layer_group_size"])
                          for l in layers),
        kda=KdaSizes(head_dim=config["head_dim"],
                     conv=config["short_conv_kernel_size"],
                     lower_bound=float(config["kda_lower_bound"])),
        latent=LatentSizes(
            q_lora_rank=None, kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            rope_theta=float(config["rope_theta"]), qk_norm=True,
            head_gate=True),
        experts=ExpertSizes(
            num_experts=deployment["router_outputs"],
            top_k=config["num_experts_per_tok"],
            width=config["moe_intermediate_size"],
            num_shared=(config["moe_shared_expert_intermediate_size"]
                        // config["moe_intermediate_size"]),
            scale=config["routed_scaling_factor"],
            held=(deployment["experts_held_first"], config["num_experts"]),
            first_dense=config["first_k_dense_replace"],
            n_group=config["n_group"], topk_group=config["topk_group"],
            train_router=bool(deployment["router_trains"])))
    return LinearLatentMoE(TransformerLM(cfg), traffic["batch"],
                           traffic["seq_len"])
