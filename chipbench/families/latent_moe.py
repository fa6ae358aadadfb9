"""Family ``latent_moe``: a DeepSeek-V3-style decoder through the repo's one
trunk (``bluefog_tpu.models.TransformerLM`` with ``attention="latent"``,
``ffn="routed+shared"``, RMSNorm, rotary, a multi-token-prediction module)
at the widths the configuration file gives, holding this chip's share of the
routed experts and of the vocabulary; next-token and next-next-token cross
entropy on seeded random tokens.  Brings ``reference_loss``: the plain model
of ``chipbench/latent_moe_reference.py``."""

import dataclasses

import jax
import jax.numpy as jnp

from bluefog_tpu.models.transformer import (
    ExpertSizes, GPTConfig, LatentSizes, TransformerLM, next_token_loss)

from chipbench import latent_moe_flops, latent_moe_reference

# what the family computes; a configuration that asks for anything else
# is refused, not approximated
FIXED = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
         "topk_group": 1, "norm_topk_prob": True, "hidden_act": "silu",
         "rope_interleave": True, "rope_scaling": None,
         "attention_bias": False, "tie_word_embeddings": False,
         "moe_layer_freq": 1}


@dataclasses.dataclass(frozen=True)
class LatentMoE:
    model: TransformerLM
    mtp_weight: float
    batch: int
    seq_len: int
    item = "tokens"

    @property
    def items_per_step(self) -> int:
        return self.batch * self.seq_len

    def init(self, key):
        # the shapes of the parameters do not depend on the length
        tokens = jnp.zeros((1, 16), jnp.int32)
        variables = self.model.init(key, tokens, next_tokens=tokens)
        return variables["params"], {"buffers": variables["buffers"]}

    def make_batch(self, key):
        return jax.random.randint(
            key, (self.batch, self.seq_len + 1 + self.model.cfg.mtp_depth),
            0, self.model.cfg.vocab_size, dtype=jnp.int32)

    def loss(self, params, model_state, batch):
        loss = next_token_loss(self.model, params, model_state, batch,
                               mtp_weight=self.mtp_weight)
        return loss, model_state

    def reference_loss(self, params, model_state, batch):
        cfg = self.model.cfg
        return latent_moe_reference.loss(
            {"heads": cfg.num_heads, "qk_nope": cfg.latent.qk_nope_head_dim,
             "qk_rope": cfg.latent.qk_rope_head_dim,
             "rope_theta": cfg.latent.rope_theta, "eps": cfg.norm_eps,
             "top_k": cfg.experts.top_k, "scale": cfg.experts.scale,
             "held_first": cfg.experts.held[0],
             "mtp_weight": self.mtp_weight},
            params, model_state, batch)

    def _shapes(self) -> dict:
        cfg = self.model.cfg
        la, ex = cfg.latent, cfg.experts
        return dict(
            hidden=cfg.hidden_size, heads=cfg.num_heads,
            q_rank=la.q_lora_rank, kv_rank=la.kv_lora_rank,
            nope=la.qk_nope_head_dim, rope=la.qk_rope_head_dim,
            v_dim=la.v_head_dim, seq_len=self.seq_len,
            dense_blocks=ex.first_dense,
            expert_blocks=cfg.num_layers - ex.first_dense,
            dense_width=cfg.ffn_width, expert_width=ex.width,
            shared_experts=ex.num_shared, router_outputs=ex.num_experts,
            top_k=ex.top_k, experts_held=ex.held[1],
            vocab_rows=cfg.vocab_size, mtp_modules=cfg.mtp_depth)

    def flops_per_item(self) -> float:
        """Forward + backward of one token; the held experts at the uniform
        expectation of ``top_k * held / router outputs`` assignments a
        token (0.5 for 16 of 256 at top-8)."""
        return latent_moe_flops.train_flops_per_token(**self._shapes())

    def kernel_costs(self) -> dict:
        """Per step and chip, by the name a metric's ``params`` asks for."""
        cfg = self.model.cfg
        la, ex = cfg.latent, cfg.experts
        layers = cfg.num_layers + cfg.mtp_depth
        calls = 2 if cfg.remat else 1
        itemsize = jnp.dtype(cfg.dtype).itemsize
        expected_rows = (self.items_per_step * ex.top_k * ex.held[1]
                         / ex.num_experts)
        return {
            "mla_attention": latent_moe_flops.mla_attention_cost(
                self.batch, cfg.num_heads, self.seq_len,
                la.qk_nope_head_dim + la.qk_rope_head_dim, la.v_head_dim,
                layers=layers, forward_calls=calls, itemsize=itemsize),
            "grouped_matmul": latent_moe_flops.grouped_matmul_cost(
                expected_rows, cfg.hidden_size, ex.width,
                layers=layers - ex.first_dense, forward_calls=calls,
                itemsize=itemsize, experts_held=ex.held[1])}


def build(config: dict, traffic: dict) -> LatentMoE:
    for key, value in FIXED.items():
        if config[key] != value:
            raise SystemExit(f"chipbench: family latent_moe computes "
                             f"{key}={value!r}, the configuration asks for "
                             f"{config[key]!r}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise SystemExit(
            f"chipbench: seq_len {traffic['seq_len']} exceeds the "
            f"configuration's {config['max_position_embeddings']} positions")
    deployment = config["deployment"]
    cfg = GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        max_position=config["max_position_embeddings"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(traffic["remat"]), attention="latent",
        ffn="routed+shared", norm="rmsnorm", position="rotary",
        ffn_width=config["intermediate_size"],
        norm_eps=config["rms_norm_eps"],
        latent=LatentSizes(
            q_lora_rank=config["q_lora_rank"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            rope_theta=float(config["rope_theta"])),
        experts=ExpertSizes(
            num_experts=deployment["router_outputs"],
            top_k=config["num_experts_per_tok"],
            width=config["moe_intermediate_size"],
            num_shared=config["n_shared_experts"],
            scale=config["routed_scaling_factor"],
            held=(deployment["experts_held_first"],
                  config["n_routed_experts"]),
            first_dense=config["first_k_dense_replace"]),
        mtp_depth=config["num_nextn_predict_layers"])
    return LatentMoE(TransformerLM(cfg), config["mtp_loss_weight"],
                     traffic["batch"], traffic["seq_len"])
