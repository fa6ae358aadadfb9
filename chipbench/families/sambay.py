"""Family ``sambay``: a decoder-hybrid-decoder (SambaY, arXiv:2507.06607; the
architecture of Phi-4-mini-flash-reasoning) through the repo's one trunk
(``bluefog_tpu.models.TransformerLM`` with ``layer_types``: Mamba mixers,
differential attention under a window and in full, gated memory units and
cross attention that read one layer's scan output and one layer's keys and
values, the head tied to the embedding) at the widths the configuration file
gives, holding a contiguous run of the published layers and this chip's
slice of the vocabulary; next-token cross entropy on seeded random tokens.
Brings ``reference_loss``: the plain model of
``chipbench/sambay_reference.py``."""

import dataclasses

import jax
import jax.numpy as jnp

from bluefog_tpu.models.transformer import (
    GPTConfig, HybridSizes, TransformerLM, next_token_loss)

from chipbench import sambay_flops, sambay_reference

# what the family computes; a configuration that asks for anything else
# is refused, not approximated
FIXED = {"model_type": "phi4flash", "hidden_act": "silu", "mb_per_layer": 2,
         "tie_word_embeddings": True, "mlp_bias": False,
         "lm_head_bias": False, "embd_pdrop": 0, "resid_pdrop": 0}


def layer_kind(layer: int, published_layers: int, mb_per_layer: int) -> str:
    """The mixer of published layer ``layer``: the self-decoder (the first
    half and two more layers) alternates Mamba and attention, windowed but
    for its last attention layer, whose keys and values the cross-decoder
    reads; the cross-decoder alternates gated memory units (over the last
    Mamba layer's memory) and cross attention."""
    half = published_layers // 2
    if layer % mb_per_layer == 0:
        return "mamba" if layer < half + 2 else "gmu"
    if layer < half + 1:
        return "diff_attention_window"
    return "diff_attention" if layer == half + 1 else "cross_diff_attention"


@dataclasses.dataclass(frozen=True)
class Sambay:
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
        return self.model.init(key, tokens)["params"], {}

    def make_batch(self, key):
        return jax.random.randint(
            key, (self.batch, self.seq_len + 1), 0,
            self.model.cfg.vocab_size, dtype=jnp.int32)

    def loss(self, params, model_state, batch):
        return next_token_loss(self.model, params, model_state,
                               batch), model_state

    def reference_loss(self, params, model_state, batch):
        cfg = self.model.cfg
        return sambay_reference.loss(
            {"kinds": cfg.layer_types, "first_layer": cfg.hybrid.first_layer,
             "head_dim": cfg.hidden_size // cfg.num_heads,
             "window": cfg.hybrid.window, "d_state": cfg.hybrid.d_state,
             "eps": cfg.norm_eps}, params, batch)

    def flops_per_item(self) -> float:
        """Forward + backward of one token: matrix work only (the scan is
        vector work and is in no model FLOP count; ``sambay_flops``)."""
        cfg, hy = self.model.cfg, self.model.cfg.hybrid
        return sambay_flops.train_flops_per_token(
            kinds=cfg.layer_types, hidden=cfg.hidden_size,
            ffn_width=cfg.ffn_width, vocab_rows=cfg.vocab_size,
            heads=cfg.num_heads, kv_heads=hy.kv_heads, inner=hy.d_inner,
            state=hy.d_state, dt_rank=hy.dt_rank, seq_len=self.seq_len,
            window=hy.window)

    def kernel_costs(self) -> dict:
        """Per step and chip, by the name a metric's ``params`` asks for."""
        cfg, hy = self.model.cfg, self.model.cfg.hybrid
        calls = 2 if cfg.remat else 1
        itemsize = jnp.dtype(cfg.dtype).itemsize
        windows = [hy.window if kind == "diff_attention_window" else None
                   for kind in cfg.layer_types if "attention" in kind]
        return {
            "diff_attention": sambay_flops.diff_attention_cost(
                self.batch, cfg.num_heads, hy.kv_heads, self.seq_len,
                cfg.hidden_size // cfg.num_heads, windows=windows,
                forward_calls=calls, itemsize=itemsize),
            "selective_scan": sambay_flops.selective_scan_cost(
                self.batch, self.seq_len, hy.d_inner, hy.d_state,
                layers=cfg.layer_types.count("mamba"), forward_calls=calls,
                itemsize=itemsize)}


def build(config: dict, traffic: dict) -> Sambay:
    for key, value in FIXED.items():
        if config[key] != value:
            raise SystemExit(f"chipbench: family sambay computes "
                             f"{key}={value!r}, the configuration asks for "
                             f"{config[key]!r}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise SystemExit(
            f"chipbench: seq_len {traffic['seq_len']} exceeds the "
            f"configuration's {config['max_position_embeddings']} positions")
    deployment, mamba = config["deployment"], config["mamba"]
    first = deployment["first_layer"]
    kinds = tuple(
        layer_kind(first + i, deployment["published"]["num_hidden_layers"],
                   config["mb_per_layer"])
        for i in range(config["num_hidden_layers"]))
    cfg = GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        max_position=config["max_position_embeddings"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(traffic["remat"]), ffn="swiglu", position="none",
        ffn_width=config["intermediate_size"],
        norm_eps=config["layer_norm_eps"], layer_types=kinds, tie_head=True,
        hybrid=HybridSizes(
            d_inner=mamba["expand"] * config["hidden_size"],
            d_state=mamba["d_state"], d_conv=mamba["d_conv"],
            dt_rank=mamba["dt_rank"],
            kv_heads=config["num_key_value_heads"],
            window=config["sliding_window"], first_layer=first))
    return Sambay(TransformerLM(cfg), traffic["batch"], traffic["seq_len"])
