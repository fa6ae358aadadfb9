"""Family ``mamba2_gqa_moe``: a Nemotron-H-style decoder (the language model
of nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16) through the repo's one trunk
(``bluefog_tpu.models.TransformerLM`` with ``attention="grouped_query"``,
``layer_types`` of ``mamba2`` layers, un-positioned ``full_attention`` layers
and ``feed_forward`` blocks, so that every block is one norm and one
sub-layer; ``ffn="routed+shared"`` with a sigmoid top-k router that has a
selection bias, ungated relu-squared experts and one shared expert of a
width of its own; RMSNorm, an untied head) at the widths the configuration
file gives, holding a contiguous run of the published blocks, this chip's
share of the routed experts and its slice of the vocabulary; next-token
cross entropy on seeded random tokens.  Brings ``reference_loss``: the plain
model of ``chipbench/mamba2_gqa_moe_reference.py``."""

import dataclasses

import jax
import jax.numpy as jnp

try:
    from bluefog_tpu.models.transformer import Mamba2Sizes
except ImportError:
    raise SystemExit(
        "chipbench: family mamba2_gqa_moe needs a program whose TransformerLM "
        "builds 'mamba2' layers (bluefog_tpu.models.transformer.Mamba2Sizes); "
        "this checkout has none") from None
from bluefog_tpu.models.transformer import (
    ExpertSizes, GPTConfig, GroupedSizes, TransformerLM, next_token_loss)
from bluefog_tpu.ops.ssd import CHUNK

from chipbench import (
    gqa_moe_flops, mamba2_gqa_moe_flops, mamba2_gqa_moe_reference)

# what the family computes; a configuration that asks for anything else
# is refused, not approximated
FIXED = {"model_type": "nemotron_h", "mlp_hidden_act": "relu2",
         "mamba_hidden_act": "silu", "use_conv_bias": True, "use_bias": False,
         "mamba_proj_bias": False, "attention_bias": False, "mlp_bias": False,
         "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
         "n_shared_experts": 1, "tie_word_embeddings": False,
         "chunk_size": CHUNK, "sliding_window": None}
# the pattern's letter -> the trunk's layer type
KINDS = {"M": "mamba2", "*": "full_attention", "E": "feed_forward"}
WEIGHT_EPS = 1e-20    # of the router's normaliser, in the modelling code


@dataclasses.dataclass(frozen=True)
class Mamba2GqaMoe:
    model: TransformerLM
    kinds: str            # the pattern's letters of the blocks held
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
        ex, ssm = cfg.experts, cfg.mamba2
        return mamba2_gqa_moe_reference.loss(
            {"kinds": self.kinds, "head_dim": cfg.grouped.head_dim,
             "eps": cfg.norm_eps, "mamba_heads": ssm.heads,
             "mamba_groups": ssm.groups, "mamba_state": ssm.state,
             "top_k": ex.top_k, "scale": ex.scale,
             "weight_eps": ex.weight_eps, "held_first": ex.held[0],
             "train_router": ex.train_router},
            params, model_state, batch)

    def _shapes(self) -> dict:
        cfg = self.model.cfg
        gq, ex, ssm = cfg.grouped, cfg.experts, cfg.mamba2
        return dict(
            kinds=self.kinds, hidden=cfg.hidden_size, mamba_heads=ssm.heads,
            mamba_head_dim=ssm.head_dim, state=ssm.state, groups=ssm.groups,
            heads=cfg.num_heads, kv_heads=gq.kv_heads, head_dim=gq.head_dim,
            seq_len=self.seq_len, router_outputs=ex.num_experts,
            top_k=ex.top_k, experts_held=ex.held[1], expert_width=ex.width,
            shared_width=ex.shared_width, vocab_rows=cfg.vocab_size)

    def flops_per_item(self) -> float:
        """Forward + backward of one token; the held experts at the uniform
        expectation of ``top_k * held / router outputs`` assignments a
        token (0.375 for 8 of 128 at top-6), the scans as the recurrence."""
        return mamba2_gqa_moe_flops.train_flops_per_token(**self._shapes())

    def kernel_costs(self) -> dict:
        """Per step and chip, by the name a metric's ``params`` asks for."""
        cfg = self.model.cfg
        gq, ex, ssm = cfg.grouped, cfg.experts, cfg.mamba2
        calls = 2 if cfg.remat else 1
        itemsize = jnp.dtype(cfg.dtype).itemsize
        expected_rows = (self.items_per_step * ex.top_k * ex.held[1]
                         / ex.num_experts)
        count = self.kinds.count
        return {
            "attention": gqa_moe_flops.gqa_attention_cost(
                self.batch, cfg.num_heads, gq.kv_heads, self.seq_len,
                gq.head_dim,
                windows=[None] * count(mamba2_gqa_moe_flops.ATTENTION),
                forward_calls=calls, itemsize=itemsize),
            "grouped_matmul":
                mamba2_gqa_moe_flops.ungated_grouped_matmul_cost(
                    expected_rows, cfg.hidden_size, ex.width,
                    layers=count(mamba2_gqa_moe_flops.EXPERTS),
                    forward_calls=calls, itemsize=itemsize,
                    experts_held=ex.held[1]),
            "ssd": mamba2_gqa_moe_flops.ssd_cost(
                self.batch, self.seq_len, ssm.heads, ssm.head_dim, ssm.state,
                ssm.groups, layers=count(mamba2_gqa_moe_flops.MAMBA),
                forward_calls=calls, itemsize=itemsize)}


def build(config: dict, traffic: dict) -> Mamba2GqaMoe:
    for key, value in FIXED.items():
        if config[key] != value:
            raise SystemExit(f"chipbench: family mamba2_gqa_moe computes "
                             f"{key}={value!r}, the configuration asks for "
                             f"{config[key]!r}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise SystemExit(
            f"chipbench: seq_len {traffic['seq_len']} exceeds the "
            f"configuration's {config['max_position_embeddings']} positions")
    deployment = config["deployment"]
    published = deployment["published"]
    first, layers = deployment["first_layer"], config["num_hidden_layers"]
    kinds = config["hybrid_override_pattern"]
    if kinds != published["hybrid_override_pattern"][first:first + layers]:
        raise SystemExit(
            "chipbench: hybrid_override_pattern is the published pattern's "
            f"letters {first}..{first + layers - 1} "
            "(deployment.first_layer on)")
    unknown = set(kinds) - set(KINDS)
    if unknown:
        raise SystemExit(f"chipbench: family mamba2_gqa_moe computes the "
                         f"blocks {sorted(KINDS)}; the configuration has "
                         f"{sorted(unknown)}")
    cfg = GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=layers, num_heads=config["num_attention_heads"],
        max_position=config["max_position_embeddings"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(traffic["remat"]), attention="grouped_query",
        ffn="routed+shared", norm="rmsnorm", position="none",
        norm_eps=config["layer_norm_epsilon"], tie_head=False,
        layer_types=tuple(KINDS[kind] for kind in kinds),
        grouped=GroupedSizes(
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            window=config["max_position_embeddings"],   # no layer has one
            rope_theta=float(config["rope_theta"])),    # and none turns
        mamba2=Mamba2Sizes(
            heads=config["mamba_num_heads"],
            head_dim=config["mamba_head_dim"],
            state=config["ssm_state_size"], groups=config["n_groups"],
            conv=config["conv_kernel"]),
        experts=ExpertSizes(
            num_experts=deployment["router_outputs"],
            top_k=config["num_experts_per_tok"],
            width=config["moe_intermediate_size"],
            num_shared=config["n_shared_experts"],
            scale=float(config["routed_scaling_factor"]),
            held=(deployment["experts_held_first"],
                  config["n_routed_experts"]),
            first_dense=0, activation=config["mlp_hidden_act"], gated=False,
            shared_width=config["moe_shared_expert_intermediate_size"],
            train_router=bool(deployment["router_trains"]),
            weight_eps=WEIGHT_EPS))
    return Mamba2GqaMoe(TransformerLM(cfg), kinds, traffic["batch"],
                        traffic["seq_len"])
