"""Family ``decoder``: the repo's dense pre-LN decoder
(``bluefog_tpu.models.TransformerLM``) at the widths the configuration file
gives, next-token cross entropy on seeded random tokens."""

import dataclasses

import jax
import jax.numpy as jnp
import optax

from bluefog_tpu.models import GPTConfig, TransformerLM

from chipbench import flops


@dataclasses.dataclass(frozen=True)
class Decoder:
    model: TransformerLM
    vocab_size: int          # rows a target may name (the source's count)
    batch: int
    seq_len: int
    item = "tokens"

    @property
    def items_per_step(self) -> int:
        return self.batch * self.seq_len

    def init(self, key):
        tokens = jnp.zeros((1, self.seq_len), jnp.int32)
        return self.model.init(key, tokens)["params"], {}

    def make_batch(self, key):
        return jax.random.randint(
            key, (self.batch, self.seq_len + 1), 0, self.vocab_size,
            dtype=jnp.int32)

    def loss(self, params, model_state, batch):
        logits = self.model.apply({"params": params}, batch[:, :-1])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), batch[:, 1:]).mean()
        return loss, model_state

    def flops_per_item(self) -> float:
        cfg = self.model.cfg
        return flops.decoder_train_flops_per_token(
            cfg.hidden_size, cfg.num_layers, self.seq_len, cfg.vocab_size,
            cfg.mlp_ratio)

    def kernel_costs(self) -> dict:
        """Per step and chip, by the name a metric's ``params`` asks for."""
        cfg = self.model.cfg
        return {"causal_attention": flops.causal_attention_cost(
            self.batch, cfg.num_heads, self.seq_len,
            cfg.hidden_size // cfg.num_heads, layers=cfg.num_layers,
            forward_calls=2 if cfg.remat else 1,
            itemsize=jnp.dtype(cfg.dtype).itemsize)}


def build(config: dict, traffic: dict) -> Decoder:
    if traffic["seq_len"] > config["n_positions"]:
        raise SystemExit(
            f"chipbench: seq_len {traffic['seq_len']} exceeds the "
            f"configuration's {config['n_positions']} positions")
    cfg = GPTConfig(
        vocab_size=config["padded_vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        mlp_ratio=config["mlp_ratio"], max_position=config["n_positions"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(traffic["remat"]))
    return Decoder(TransformerLM(cfg), config["vocab_size"],
                   traffic["batch"], traffic["seq_len"])
