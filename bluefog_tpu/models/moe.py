"""Mixture-of-experts transformer LM — the expert-parallel flagship variant.

No counterpart in the reference (SURVEY.md §2.3: EP absent).  Pairs
:mod:`bluefog_tpu.ops.moe` (Switch routing + all_to_all expert parallelism)
with the :class:`~bluefog_tpu.models.transformer.TransformerLM` skeleton:
every block's MLP is replaced by a Switch-MoE FFN whose experts are sharded
over the ``'ep'`` mesh axis, with tokens batch-sharded over the same axis.

**Which expert layer.**  The Switch / GShard routers here build dense
one-hot ``(T, E, C)`` dispatch tensors with a per-expert capacity: the shape
the ``all_to_all`` exchange of :func:`expert_parallel_ffn` needs (every
shard sends every other a fixed-size buffer), fine for a few wide experts,
and they drop what overflows.  For many small experts (hundreds, top-8) use
the dropless layer instead: ``GPTConfig(ffn="routed+shared",
experts=ExpertSizes(...))`` →
:class:`bluefog_tpu.models.transformer.RoutedSharedFFN` over
:func:`bluefog_tpu.ops.moe.routed_experts` (sort by expert, grouped matmuls
over the experts the chip holds in passes of a row buffer sized from the
held share, no capacity, no drop; the ``(T, E, C)`` tensors cannot hold 64
experts at 8k tokens).

Loss convention for training inside ``shard_map``: normalize by the GLOBAL
token count (see ops/moe.py docstring) so raw ``jax.grad`` is exact.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from bluefog_tpu.models.transformer import GPTConfig, TransformerLM
from bluefog_tpu.ops.moe import expert_parallel_ffn, moe_ffn_reference
from bluefog_tpu.parallel.rng import sharded_init

__all__ = ["MoEConfig", "MoEMLP", "MoETransformerLM", "moe_param_rules"]


def moe_param_rules(ep_axis: str = "ep", tp_axis: Optional[str] = None):
    """The unified :class:`~bluefog_tpu.sharding.RuleTable` for a
    :class:`MoETransformerLM`'s parameters: expert weights (``wi``/``wo``)
    sharded over ``ep_axis`` on their leading expert dim, the router
    replicated, and — with ``tp_axis`` — the attention trunk in Megatron
    placement against THIS model's naming (fused ``qkv/kernel`` sharded
    on its output dim, ``proj/kernel`` row-sharded on its input dim;
    there is no ``up``/``down`` pair, the MLP is the MoE layer) — so EP,
    TP, the optimizer state, and the gossip windows all resolve through
    ONE table."""
    from jax.sharding import PartitionSpec as P

    from bluefog_tpu.sharding.rules import Rule, RuleTable

    rules = [
        Rule(r"moe/w[io]$", P(ep_axis)),
        Rule(r"moe/router$", P()),
    ]
    if tp_axis is not None:
        rules.extend([
            Rule(r"qkv/kernel$", P(None, tp_axis)),
            Rule(r"qkv/bias$", P(tp_axis)),
            Rule(r"proj/kernel$", P(tp_axis, None)),
        ])
    # explicit replicate tail: embeddings, layernorms, lm_head,
    # row-parallel biases — replication is a decision, not a leak
    rules.append(Rule(".*", P()))
    return RuleTable(rules)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Switch-MoE hyperparameters on top of a :class:`GPTConfig`: one-hot
    routing with a static capacity (tokens past it are dropped), for the
    expert-parallel ``all_to_all`` path.  Many small experts take the
    dropless layer (module docstring)."""

    gpt: GPTConfig
    num_experts: int = 8
    ep_size: int = 1
    ep_axis: str = "ep"
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    router: str = "top1"  # 'top1' (Switch) or 'top2' (GShard)

    def __post_init__(self):
        if self.router not in ("top1", "top2"):
            raise ValueError(
                f"unknown router {self.router!r}; expected 'top1' or 'top2'")
        if self.router == "top2" and self.num_experts < 2:
            raise ValueError(
                f"router='top2' requires num_experts >= 2, got "
                f"{self.num_experts} (the second choice would duplicate "
                "the first and silently halve capacity)")

    @staticmethod
    def tiny(ep_size: int = 1, router: str = "top1") -> "MoEConfig":
        return MoEConfig(gpt=GPTConfig.tiny(), num_experts=4,
                         ep_size=ep_size, capacity_factor=2.0, router=router)

    def capacity(self, tokens_per_shard: int) -> int:
        # top-2 makes two assignments per token: scale capacity with k so
        # capacity_factor keeps meaning "headroom over a perfect balance"
        k = 2 if self.router == "top2" else 1
        c = int(self.capacity_factor * k * tokens_per_shard
                / self.num_experts)
        return max(c, 1)


class MoEMLP(nn.Module):
    """Switch-MoE FFN; expert weights sharded over ``cfg.ep_axis`` when
    ``cfg.ep_size > 1`` (params hold only the local experts), dense reference
    path when ``ep_size == 1``.  Still the layer for experts spread over
    chips with the exchange inside the step; the dropless
    :class:`~bluefog_tpu.models.transformer.RoutedSharedFFN` computes one
    chip's share without an exchange (module docstring)."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gpt = cfg.gpt
        if cfg.num_experts % cfg.ep_size:
            raise ValueError(
                f"experts {cfg.num_experts} % ep {cfg.ep_size}")
        local_e = cfg.num_experts // cfg.ep_size
        hidden = gpt.mlp_ratio * gpt.hidden_size
        fold = cfg.ep_axis if cfg.ep_size > 1 else None

        router = self.param("router", nn.initializers.lecun_normal(),
                            (gpt.hidden_size, cfg.num_experts), jnp.float32)
        wi = self.param(
            "wi", sharded_init(
                nn.initializers.lecun_normal(in_axis=1, out_axis=2), fold),
            (local_e, gpt.hidden_size, hidden), jnp.float32)
        wo = self.param(
            "wo", sharded_init(
                nn.initializers.lecun_normal(in_axis=1, out_axis=2), fold),
            (local_e, hidden, gpt.hidden_size), jnp.float32)

        B, T, D = x.shape
        flat = x.reshape(B * T, D)
        cap = cfg.capacity(B * T)
        if cfg.ep_size == 1:
            y, aux, metrics = moe_ffn_reference(
                flat, router, wi.astype(gpt.dtype), wo.astype(gpt.dtype),
                num_experts=cfg.num_experts, capacity=cap,
                router=cfg.router)
        else:
            y, aux, metrics = expert_parallel_ffn(
                flat, router, wi.astype(gpt.dtype), wo.astype(gpt.dtype),
                ep_axis=cfg.ep_axis, num_experts=cfg.num_experts,
                capacity=cap, router=cfg.router)
        self.sow("aux_loss", "moe", aux)
        # drop/load accounting (stop-gradiented in the router): collect
        # with mutable=["moe_metrics"] — the bench surfaces dropped_frac
        self.sow("moe_metrics", "dropped_frac", metrics["dropped_frac"])
        self.sow("moe_metrics", "fully_dropped_frac",
                 metrics["fully_dropped_frac"])
        return y.reshape(B, T, D)


def MoETransformerLM(cfg: MoEConfig) -> TransformerLM:
    """Switch-MoE decoder LM: the :class:`TransformerLM` trunk with every
    block's MLP swapped for a :class:`MoEMLP` (one shared attention/embedding
    implementation — no duplicated trunk).

    Inside ``shard_map`` over an ``'ep'`` axis, pass the per-shard token
    batch; collect the aux loss via ``mutable=["aux_loss"]`` and add
    ``cfg.aux_loss_weight * sum``.  Gradient convention for replicated vs
    ep-sharded params: see the ops/moe.py module docstring.
    """
    return TransformerLM(cfg.gpt, mlp=lambda: MoEMLP(cfg, name="moe"))
