"""Family ``looped_decoder``: an Ouro-style looped language model
(arXiv:2510.25741; ByteDance/Ouro-2.6B) through the repo's one trunk
(``bluefog_tpu.models.TransformerLM`` with ``rounds`` passes of the same
blocks, ``sandwich_norm``, an ``exit_gate``, ``attention="grouped_query"``
in full rotary layers, ``ffn="swiglu"``, RMSNorm, an untied head) at the
widths the configuration file gives, holding a contiguous run of the
published layers and the whole vocabulary; the expected next-token loss
over the exits less ``beta`` times the exit distribution's entropy
(``next_token_loss``) on seeded random tokens.  Brings ``reference_loss``:
the plain model of ``chipbench/looped_decoder_reference.py``."""

import dataclasses

import jax
import jax.numpy as jnp

try:
    from bluefog_tpu.models.transformer import exit_distribution  # noqa: F401
except ImportError:
    raise SystemExit(
        "chipbench: family looped_decoder needs a program whose TransformerLM "
        "runs its blocks several times over the same leaves with an exit "
        "gate (bluefog_tpu.models.transformer.GPTConfig.rounds / "
        ".sandwich_norm / .exit_gate and exit_distribution); this checkout "
        "has none") from None
from bluefog_tpu.models.transformer import (
    GPTConfig, GroupedSizes, TransformerLM, next_token_loss)

from chipbench import looped_decoder_flops, looped_decoder_reference

# what the family computes; a configuration that asks for anything else
# is refused, not approximated
FIXED = {"model_type": "ouro", "hidden_act": "silu", "rope_scaling": None,
         "sliding_window": None, "use_sliding_window": False,
         "tie_word_embeddings": False}


@dataclasses.dataclass(frozen=True)
class LoopedDecoder:
    model: TransformerLM
    beta: float
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
        return next_token_loss(self.model, params, model_state, batch,
                               exit_entropy_weight=self.beta), model_state

    def reference_loss(self, params, model_state, batch):
        cfg = self.model.cfg
        return looped_decoder_reference.loss(
            {"rounds": cfg.rounds, "head_dim": cfg.grouped.head_dim,
             "rope_theta": cfg.grouped.rope_theta, "eps": cfg.norm_eps,
             "beta": self.beta}, params, batch)

    def _shapes(self) -> dict:
        cfg = self.model.cfg
        return dict(heads=cfg.num_heads, kv_heads=cfg.grouped.kv_heads,
                    head_dim=cfg.grouped.head_dim, seq_len=self.seq_len)

    def flops_per_item(self) -> float:
        """Forward + backward of one token: ``rounds * layers`` block passes
        and ``rounds`` exits; nothing recomputed."""
        cfg = self.model.cfg
        return looped_decoder_flops.train_flops_per_token(
            rounds=cfg.rounds, layers=cfg.num_layers,
            vocab_rows=cfg.vocab_size, hidden=cfg.hidden_size,
            ffn_width=cfg.ffn_width, **self._shapes())

    def kernel_costs(self) -> dict:
        """Per step and chip, by the name a metric's ``params`` asks for."""
        cfg = self.model.cfg
        return {"attention": looped_decoder_flops.attention_cost(
            self.batch, rounds=cfg.rounds, layers=cfg.num_layers,
            forward_calls=2 if cfg.remat else 1,
            itemsize=jnp.dtype(cfg.dtype).itemsize, **self._shapes())}


def build(config: dict, traffic: dict) -> LoopedDecoder:
    for key, value in FIXED.items():
        if config[key] != value:
            raise SystemExit(f"chipbench: family looped_decoder computes "
                             f"{key}={value!r}, the configuration asks for "
                             f"{config[key]!r}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise SystemExit(
            f"chipbench: seq_len {traffic['seq_len']} exceeds the "
            f"configuration's {config['max_position_embeddings']} positions")
    first = config["deployment"]["first_layer"]
    kinds = config["layer_types"][first:first + config["num_hidden_layers"]]
    if set(kinds) != {"full_attention"}:
        raise SystemExit(
            "chipbench: family looped_decoder computes full causal rotary "
            f"attention in every block; the configuration's layers {first} "
            f"to {first + len(kinds) - 1} are {sorted(set(kinds))}")
    cfg = GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        max_position=config["max_position_embeddings"],
        dtype=jnp.dtype(config["compute_dtype"]),
        remat=bool(traffic["remat"]), attention="grouped_query",
        ffn="swiglu", norm="rmsnorm", position="none",
        ffn_width=config["intermediate_size"],
        norm_eps=config["rms_norm_eps"],
        layer_types=("full_rotary_attention",) * len(kinds),
        grouped=GroupedSizes(
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"], window=0,
            rope_theta=float(config["rope_theta"])),
        rounds=config["total_ut_steps"], sandwich_norm=True, exit_gate=True)
    return LoopedDecoder(TransformerLM(cfg), config["exit_entropy_weight"],
                         traffic["batch"], traffic["seq_len"])
