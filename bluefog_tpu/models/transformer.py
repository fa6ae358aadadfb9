"""Decoder-only transformer LM — the long-context flagship.

No counterpart exists in the reference (it predates LLMs; SURVEY.md §5
"long-context": absent) — this model exists to exercise the framework's
first-class sequence parallelism: the attention core is *pluggable*, so the
same module runs

- single-device / data-parallel with plain causal attention, or
- sequence-parallel inside ``shard_map`` with
  :func:`bluefog_tpu.ops.ring_attention.ring_attention` (KV ring over ICI) or
  :func:`~bluefog_tpu.ops.ring_attention.all_to_all_attention` (Ulysses),
  passing ``position_offset = rank * T_local`` for the sharded positions.

TPU-first: bf16 activations/matmuls with f32 layernorm + softmax-accumulate,
fused QKV, static shapes, dims sized for 128-lane MXU tiles.

**One block, assembled from the configuration** (ROADMAP D7).
:class:`GPTConfig` names the kind of each sub-layer; the defaults are the
GPT-2 decoder this file always built, parameter for parameter:

===============  ===========================================================
``attention``    ``fused_qkv`` (one biased projection to q, k, v of one
                 width), ``latent`` (:class:`LatentAttention`: low-rank
                 queries and keys/values, a rotary part of the key shared
                 by the heads, values narrower than keys) or
                 ``grouped_query`` (:class:`GroupedQueryAttention`: unbiased
                 projections to ``num_heads`` query and ``grouped.kv_heads``
                 key/value heads of ``grouped.head_dim``, which need not be
                 ``hidden_size / num_heads``; what each layer attends over
                 is its entry of ``layer_types``)
``ffn``          ``gelu`` (biased, ``mlp_ratio`` wide), ``swiglu``
                 (:class:`GatedMLP`, ``ffn_width`` wide) or
                 ``routed+shared`` (:class:`RoutedFFN`, stated by
                 :class:`ExpertSizes`: the router, whether it trains, the
                 gate's activation, shared experts or none, and whether the
                 router reads the feed-forward's input or the block's; the
                 first ``experts.first_dense`` blocks take ``swiglu``)
``norm``         ``layernorm`` or ``rmsnorm`` (scale only, ``norm_eps``)
``position``     ``learned`` (a table added to the embedding), ``rotary``
                 (no table; the latent attention turns its rotary part) or
                 ``none`` (with ``layer_types``: each layer's type says
                 whether it turns its queries and keys)
``mtp_depth``    0, or 1 for one multi-token-prediction module sharing the
                 embedding and the head (:func:`next_token_loss`)
``layer_types``  ``None``, or **what each block mixes its tokens with**.
                 Either plain attention layers of the ``grouped_query``
                 kind (sizes in :class:`GroupedSizes`; the three kinds are
                 stated at :class:`GroupedQueryAttention`):
                 ``full_attention`` (causal over all keys, **no positional
                 encoding**), ``full_rotary_attention`` (all keys, queries
                 and keys turned by rotary over the whole head) and
                 ``window_rotary_attention`` (the last ``grouped.window``
                 keys, turned likewise), with ``grouped.qk_norm`` an
                 RMSNorm a head on queries and keys before the turn; among
                 them, or alone, ``short_conv`` (:class:`ShortConv`, a
                 gated causal depthwise convolution of ``short_conv.taps``
                 taps: LFM2's operator, neither an attention nor a
                 recurrence; sizes in :class:`ShortConvSizes`) and
                 ``mamba2`` (:class:`Mamba2Mixer`, Mamba-2's state-space
                 layer with a scalar decay a head; sizes in
                 :class:`Mamba2Sizes`); any ``ffn`` and ``norm`` go with
                 them.  Also among them, ``feed_forward``: **a block that
                 is the feed-forward alone**, ``x + FFN(norm(x))`` with no
                 mixer.  A model that names one is a model of
                 single-sub-layer blocks: its other blocks are a mixer
                 alone, ``x + mixer(norm(x))``, and carry no feed-forward
                 (Nemotron-H's ``M``, ``*`` and ``E`` blocks).
                 Or a linear-attention / latent-attention hybrid
                 (Kimi Linear, arXiv:2510.26692; sizes in :class:`KdaSizes`
                 and :class:`LatentSizes`): ``kda`` (:class:`KdaMixer`, the
                 delta rule with a per-channel decay) and
                 ``latent_attention`` (:class:`LatentAttention`); with them
                 ``attention="latent"`` and ``position="rotary"`` (the
                 latent layers turn their rotary part, the recurrence
                 orders the tokens elsewhere) and any ``ffn`` and ``norm``.
                 Or the mixers of a decoder-hybrid-decoder (SambaY,
                 arXiv:2507.06607; sizes in :class:`HybridSizes`): ``mamba``
                 (:class:`MambaMixer`, a selective state space),
                 ``diff_attention`` and ``diff_attention_window``
                 (:class:`DiffAttention`: two softmax maps a head pair, over
                 all keys or the last ``window``), ``gmu``
                 (:class:`GatedMemoryUnit`, which reads the memory the last
                 ``mamba`` block left) and ``cross_diff_attention`` (queries
                 of its own over the keys and values of the last
                 ``diff_attention`` block).  Those blocks hand these tensors
                 on, and with those mixers come ``ffn="swiglu"`` and
                 ``norm_eps`` for the LayerNorms.  The attention layers and
                 the SambaY mixers take ``position="none"``
``tie_head``     the head is the embedding's transpose: one leaf, whose
                 gradient is the sum of both uses
``heads_held``   ``None``, or this chip's share of the heads (below)
``rounds``       1, or **how many times the stack of blocks runs over the
                 same leaves** (a looped / universal transformer; Ouro,
                 arXiv:2510.25741): ``x_r = ln_f(blocks(x_{r-1}))``, the
                 final norm once a round, its output the next round's
                 input.  The ``num_layers`` blocks, ``ln_f``, the embedding
                 and the head are one leaf each whatever ``rounds`` is, and a
                 leaf's gradient is the sum over its uses.  What a round
                 does not carry is refused: the SambaY mixers' ``carried``
                 tensors, an MTP module, the sigmoid router's
                 selection-bias buffer.  The rounds are one ``lax.scan``
                 (:func:`_run_rounds`): a round's passes are compiled once
``sandwich_norm``  a norm **after** each sub-layer as well as before:
                 ``x + ln1_post(mixer(ln1(x)))`` and ``x +
                 ln2_post(FFN(ln2(x)))`` (two more scales a block)
``exit_gate``    with ``rounds > 1``: one ``Linear(hidden_size, 1)`` with a
                 bias, shared by the rounds, reads each ``x_r`` in f32;
                 the model hands back every round's output and the gate's
                 logits, and :func:`next_token_loss` is the expected loss
                 over the exits (there).  A looped model has one (a loop
                 whose last round alone is read is refused: no
                 configuration runs it)
===============  ===========================================================

**A chip's share of a layer** (the one place that states it).  A layer
divided over several chips is built here as what one of them holds, and
what the absent parts would add to a token is left out, with no code
standing in for them.  *Experts*: ``experts.held = (first, count)`` names the
global experts ``first .. first + count - 1`` whose weights the parameters
hold; the router keeps all ``experts.num_experts`` outputs and its
``top_k``, and the layer's result is the sum over the chosen experts that
are held (a shared expert is every chip's alike).  *Heads*: ``heads_held =
(first, count)`` names the heads ``first .. first + count - 1`` of
``num_heads`` (the ``kda`` and ``latent_attention`` layers): the parameters
hold those heads' columns of every projection out of the model width
(queries, keys, values, decay, ``beta``, the output gate, the latent
up-projection) and their rows of the output projection, while what every
head reads stays whole (the latent down-projection, the per-head norms'
scales).  The layer's result is those heads' part of the output
projection's sum; the shares of all the chips add up to the uncut layer's
(``tests/test_linear_latent_moe.py``).  *Where neither is cut*: the
grouped-query and ``short_conv`` layers hold every head and every channel
(``heads_held`` is refused there), so a model of those layers with routed
experts is shared by its experts and its vocabulary alone: every chip
computes the mixers alike, and the expert shares add up to the uncut
layer's (``tests/test_conv_gqa_moe.py``).  The same holds with ``mamba2``
layers among them: every Mamba-2 head and group and every attention head is
held, the shared expert is every chip's alike, and sixteen chips' shares of
an ungated expert block add up to the uncut block's
(``tests/test_mamba2_gqa_moe.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from bluefog_tpu.metrics import comm as metrics_comm
from bluefog_tpu.ops.head_loss import head_loss
from bluefog_tpu.ops.kda import LOWER as KDA_LOWER, kda
from bluefog_tpu.ops.moe import (
    ACTIVATIONS, routed_experts, sigmoid_topk_router, softmax_topk_router)
from bluefog_tpu.ops.ring_attention import local_attention
from bluefog_tpu.ops.row_sums import take_rows
from bluefog_tpu.ops.selective_scan import selective_scan
from bluefog_tpu.ops.short_conv import gated_short_conv, silu_short_conv
from bluefog_tpu.ops.ssd import ssd
from bluefog_tpu.tracing import startup

AttnFn = Callable[..., jnp.ndarray]  # (q, k, v) -> (B, T, H, D)


@dataclasses.dataclass(frozen=True)
class LatentSizes:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1):
    the ranks of the query and key/value bottlenecks and a head's widths.
    A query and a key are ``qk_nope_head_dim + qk_rope_head_dim`` wide, a
    value ``v_head_dim``.  ``q_lora_rank=None``: no query bottleneck, ``q =
    W_q y``.  ``qk_norm``: an RMSNorm over each head's whole query and key
    (one scale a side, shared by the heads) before the rotary.
    ``head_gate``: the heads' outputs times ``sigmoid(W_g y)``, one scalar a
    head, before the output projection.  With ``GPTConfig.heads_held`` the
    layer holds a share of the heads (module docstring, "A chip's share")."""

    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    qk_norm: bool = False
    head_gate: bool = False


@dataclasses.dataclass(frozen=True)
class KdaSizes:
    """A ``kda`` layer (Kimi Delta Attention, arXiv:2510.26692 section 3):
    keys, queries and values ``head_dim`` wide, ``conv`` taps of the causal
    depthwise convolutions on them, and ``lower_bound``, the least log-decay
    a token and channel (``g = lower_bound * sigmoid(exp(A_log) (W_f y +
    dt_bias))``, the safe gate; the chunked kernel is built for no less
    than :data:`bluefog_tpu.ops.kda.LOWER`).  The decay projection is full
    rank and the output gate one scalar a head.  With
    ``GPTConfig.heads_held`` the layer holds a share of the heads (module
    docstring, "A chip's share")."""

    head_dim: int = 128
    conv: int = 4
    lower_bound: float = -5.0


@dataclasses.dataclass(frozen=True)
class GroupedSizes:
    """Grouped-query attention: ``kv_heads`` key/value heads serve
    ``num_heads`` query heads (head ``h`` reads ``h // (num_heads /
    kv_heads)``), every head ``head_dim`` wide whatever ``hidden_size /
    num_heads`` is.  ``window`` is the ``window_rotary_attention`` layers',
    ``rope_theta`` theirs and the ``full_rotary_attention`` layers'.
    ``qk_norm``: an RMSNorm over each head's ``head_dim`` on queries and on
    keys (one scale a side, shared by the heads) before any turn."""

    kv_heads: int
    head_dim: int
    window: int
    rope_theta: float
    qk_norm: bool = False


@dataclasses.dataclass(frozen=True)
class ShortConvSizes:
    """A ``short_conv`` layer (:class:`ShortConv`): ``taps`` of the causal
    depthwise convolution over the model width (LFM2's ``conv_L_cache``)."""

    taps: int = 3


@dataclasses.dataclass(frozen=True)
class Mamba2Sizes:
    """A ``mamba2`` layer (:class:`Mamba2Mixer`; arXiv:2405.21060):
    ``heads`` of ``head_dim`` channels (the inner width is their product,
    whatever the model's width), a state of ``state`` a channel, ``groups``
    of heads sharing one ``B`` and ``C`` (head ``h`` reads group ``h //
    (heads / groups)``; the gated norm runs over a group's channels) and
    ``conv`` taps of the causal depthwise convolution.  The chunk of the
    scan is :data:`bluefog_tpu.ops.ssd.CHUNK`: no knob."""

    heads: int = 64
    head_dim: int = 64
    state: int = 128
    groups: int = 8
    conv: int = 4


ROUTERS = ("sigmoid_noaux_tc", "softmax_topk")
ROUTER_INPUTS = ("ffn", "block")


@dataclasses.dataclass(frozen=True)
class ExpertSizes:
    """An expert layer, stated whole: the router scores all ``num_experts``,
    a token takes ``top_k``, and this chip computes the experts ``held =
    (first, count)`` for the tokens routed to them (module docstring, "A
    chip's share").

    ``router``: ``sigmoid_noaux_tc`` (DeepSeek-V3, arXiv:2412.19437 §2.1.2:
    sigmoid scores, a selection-bias buffer, the chosen scores normalised
    over their sum plus ``weight_eps`` and times ``scale``; with
    ``n_group > 1`` the selection is
    group-limited: the experts in ``n_group`` equal groups, a group's score
    the sum of its two best, the ``topk_group`` best groups kept and the
    ``top_k`` taken among theirs) or ``softmax_topk`` (a softmax over the
    chosen logits; no bias, no buffer, no ``scale``, one group).  **The
    expert's form**: ``gated`` (the default) is ``W_down (activation(W_gate
    x) * W_up x)``, three leaves; ``gated=False`` is ``W_down
    activation(W_up x)``, two leaves (no ``w_gate`` is built), the routed
    and the shared experts alike.  ``activation``: ``silu``, ``relu``
    (ReGLU) or ``relu2`` (``relu(x) ** 2``).  ``num_shared`` experts
    every token takes, as one MLP of that many widths, or ``shared_width``
    wide where that is stated; 0 builds none.
    ``first_dense`` leading blocks keep the dense ``swiglu``; 0 for none.
    ``router_input``: ``ffn``, the feed-forward's own normed input, or
    ``block``, the block's normed input that the attention reads too (the
    routing of a block is then known before its attention has run;
    SmallThinker, arXiv:2507.20984).

    ``train_router=False`` makes the routing weights constants of the
    backward pass, so the router gets no gradient (weight decay still
    reaches it).  For a chip that holds a share of the experts with no
    exchange behind it: a weight's gradient is formed from the outputs of
    all the chosen experts once the exchange has brought them back, and
    formed from the held ones alone it pulls the assignments onto them
    (PERF.md section 6, PR 34: a held share of 0.25 became 0.88 in 38
    steps)."""

    num_experts: int = 256
    top_k: int = 8
    width: int = 768               # of one expert, routed or shared
    num_shared: int = 1
    scale: float = 2.5             # routed_scaling_factor (sigmoid router)
    held: Tuple[int, int] = (0, 256)
    first_dense: int = 1           # leading blocks with the dense swiglu
    router: str = "sigmoid_noaux_tc"
    activation: str = "silu"
    router_input: str = "ffn"
    train_router: bool = True
    n_group: int = 1               # sigmoid router: groups of experts,
    topk_group: int = 1            # and how many of them a token keeps
    weight_eps: float = 0.0        # sigmoid router: added to the chosen sum
    gated: bool = True             # False: W_down act(W_up x), two leaves
    shared_width: Optional[int] = None   # None: num_shared * width


MIXERS = ("mamba", "diff_attention", "diff_attention_window", "gmu",
          "cross_diff_attention")
ATTENTION_LAYERS = ("full_attention", "window_rotary_attention",
                    "full_rotary_attention")
CONV_LAYERS = ("short_conv",)      # built beside the attention layers
SSD_LAYERS = ("mamba2",)           # likewise
FEED_FORWARD = "feed_forward"      # a block of the feed-forward alone
LINEAR_LAYERS = ("kda", "latent_attention")
ROUTED = "routed+shared"


@dataclasses.dataclass(frozen=True)
class HybridSizes:
    """What the mixers of ``layer_types`` need beyond the trunk's widths:
    Mamba-1's inner width, state, convolution taps and ``delta`` rank
    (arXiv:2312.00752), and differential attention's key/value heads and
    window (arXiv:2410.05258; a head is ``hidden_size / num_heads`` wide, a
    pair's value twice that).  ``first_layer`` is the published index of
    block 0: ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` depends on the layer."""

    d_inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    kv_heads: int = 20
    window: int = 512
    first_layer: int = 0


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # 50257 padded up to a 128 multiple
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_ratio: int = 4
    max_position: int = 8192
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False              # rematerialize each block's activations
    # (jax.checkpoint): backward recomputes the block instead of storing its
    # intermediates — O(sqrt-ish) HBM for long sequences at ~1/3 extra FLOPs
    # the block's kinds (module docstring); the defaults are GPT-2's
    attention: str = "fused_qkv"
    ffn: str = "gelu"
    norm: str = "layernorm"
    position: str = "learned"
    ffn_width: Optional[int] = None  # swiglu; None: mlp_ratio * hidden_size
    norm_eps: float = 1e-6           # either norm's
    latent: Optional[LatentSizes] = None
    experts: Optional[ExpertSizes] = None
    mtp_depth: int = 0
    layer_types: Optional[Tuple[str, ...]] = None   # a mixer a block
    hybrid: Optional[HybridSizes] = None
    tie_head: bool = False
    grouped: Optional[GroupedSizes] = None
    kda: Optional[KdaSizes] = None
    heads_held: Optional[Tuple[int, int]] = None    # (first, count)
    short_conv: Optional[ShortConvSizes] = None
    mamba2: Optional[Mamba2Sizes] = None
    rounds: int = 1                  # passes of the stack over the same leaves
    sandwich_norm: bool = False      # a norm after each sub-layer too
    exit_gate: bool = False          # rounds > 1: a gate on every round's exit

    @property
    def single_sublayer(self) -> bool:
        """Whether a block is a mixer or a feed-forward alone: a model that
        names a ``feed_forward`` block (module docstring)."""
        return FEED_FORWARD in (self.layer_types or ())

    def __post_init__(self):
        for field, kinds in (("attention", ("fused_qkv", "latent",
                                            "grouped_query")),
                             ("ffn", ("gelu", "swiglu", ROUTED)),
                             ("norm", ("layernorm", "rmsnorm")),
                             ("position", ("learned", "rotary", "none"))):
            if getattr(self, field) not in kinds:
                raise ValueError(f"unknown {field} {getattr(self, field)!r};"
                                 f" expected one of {kinds}")
        types = () if self.layer_types is None else tuple(self.layer_types)
        if self.layer_types is not None and len(types) != self.num_layers:
            raise ValueError(f"{len(types)} layer_types for "
                             f"{self.num_layers} layers")
        mixers = [kind in MIXERS for kind in types]
        linear = [kind in LINEAR_LAYERS for kind in types]
        for kind in types:
            if kind not in (MIXERS + ATTENTION_LAYERS + CONV_LAYERS
                            + SSD_LAYERS + (FEED_FORWARD,) + LINEAR_LAYERS):
                raise ValueError(
                    f"unknown layer type {kind!r} in layer_types; expected "
                    f"mixers {MIXERS}, attention layers {ATTENTION_LAYERS} "
                    f"with {CONV_LAYERS + SSD_LAYERS} and {FEED_FORWARD!r} "
                    f"blocks among them, or linear/latent layers "
                    f"{LINEAR_LAYERS}")
        for family in (mixers, linear):
            if any(family) and not all(family):
                raise ValueError(
                    "layer_types mixes layer families; a model takes the "
                    f"SambaY mixers {MIXERS}, the attention layers "
                    f"{ATTENTION_LAYERS} with {CONV_LAYERS} among them, or "
                    f"the linear/latent layers {LINEAR_LAYERS}")
        if any(mixers) != (self.hybrid is not None):
            raise ValueError("the `hybrid` sizes and the SambaY mixers in "
                             "`layer_types` come together")
        if ("kda" in types) != (self.kda is not None):
            raise ValueError("the `kda` sizes and the 'kda' layers of "
                             "`layer_types` come together")
        if ("short_conv" in types) != (self.short_conv is not None):
            raise ValueError("the `short_conv` sizes and the 'short_conv' "
                             "layers of `layer_types` come together")
        if ("mamba2" in types) != (self.mamba2 is not None):
            raise ValueError("the `mamba2` sizes and the 'mamba2' layers of "
                             "`layer_types` come together")
        if self.mamba2 and self.mamba2.heads % self.mamba2.groups:
            raise ValueError(f"mamba2: {self.mamba2.heads} heads do not "
                             f"divide over {self.mamba2.groups} groups")
        if any(linear) and (self.attention, self.position) != (
                "latent", "rotary"):
            raise ValueError(
                "the linear/latent layers of `layer_types` are built with "
                "attention='latent' and position='rotary' (the latent "
                "layers turn their rotary part; the recurrence orders the "
                "tokens elsewhere)")
        if not any(linear) and (self.position == "none") != bool(types):
            raise ValueError("position='none' is for `layer_types` (a "
                             "recurrence orders the tokens, or the layer's "
                             "type says whether it turns its keys); fused_qkv "
                             "and latent heads need positions")
        self._check_heads(any(linear))
        self._check_rounds(any(mixers))
        if any(mixers):
            self._check_mixers()
            return
        if (self.attention == "latent") != (self.latent is not None):
            raise ValueError("attention='latent' and the `latent` sizes come "
                             "together")
        if (self.attention == "grouped_query") != (self.grouped is not None):
            raise ValueError("attention='grouped_query' and the `grouped` "
                             "sizes come together")
        if (self.attention == "grouped_query") != (
                bool(types) and not any(linear)):
            raise ValueError("the attention layers of `layer_types` are the "
                             "grouped_query attention's, and it needs them: "
                             "each says whether it is windowed and rotary")
        if (self.position == "rotary") != (self.attention == "latent"):
            raise ValueError("position='rotary' is the latent attention's "
                             "(its keys carry the rotary part); fused_qkv "
                             "heads take position='learned'")
        if (self.ffn == ROUTED) != (self.experts is not None):
            raise ValueError("ffn='routed+shared' and the `experts` sizes "
                             "come together")
        if self.mtp_depth not in (0, 1) or (self.mtp_depth and types):
            raise ValueError(f"mtp_depth {self.mtp_depth}: one module or "
                             "none, and none with `layer_types` (the module's "
                             "block has no type)")
        if self.grouped and self.num_heads % self.grouped.kv_heads:
            raise ValueError(f"{self.num_heads} query heads do not divide "
                             f"over {self.grouped.kv_heads} key/value heads")
        if self.grouped and self.grouped.qk_norm and not any(
                kind in ATTENTION_LAYERS for kind in types):
            raise ValueError("grouped.qk_norm norms the queries and keys of "
                             "the attention layers; `layer_types` has none")
        if self.experts is not None:
            self._check_experts()

    def _check_rounds(self, mixers: bool):
        if self.rounds < 1 or self.exit_gate != (self.rounds > 1):
            raise ValueError(
                f"rounds {self.rounds}, exit_gate {self.exit_gate}: one "
                "pass of the stack or more, and a gate where, and only "
                "where, there are several exits to weigh (rounds > 1: no "
                "configuration runs a loop whose last round alone is read)")
        if self.rounds == 1:
            return
        sigmoid_router = (self.experts is not None
                          and self.experts.router == "sigmoid_noaux_tc")
        if mixers or self.mtp_depth or sigmoid_router:
            raise ValueError(
                f"rounds {self.rounds}: a round hands the next one the "
                "normed residual stream and nothing else, so a looped "
                "model takes no SambaY mixers (their `carried` memory, "
                "keys and values), no MTP module (mtp_depth) and no "
                "sigmoid router (its selection-bias buffer is one a "
                "layer, not one a round); nothing published says what "
                "those would be across rounds")

    def _check_heads(self, linear: bool):
        held, kda_sizes = self.heads_held, self.kda
        if held is not None and not linear:
            raise ValueError("heads_held is the linear/latent layers' (the "
                             "other attentions hold every head)")
        if held is not None and not (
                0 <= held[0] and held[1] >= 1
                and held[0] + held[1] <= self.num_heads):
            raise ValueError(f"heads_held={held} is not a range of the "
                             f"{self.num_heads} heads")
        if kda_sizes is not None and not (
                KDA_LOWER <= kda_sizes.lower_bound < 0):
            raise ValueError(
                f"kda.lower_bound {kda_sizes.lower_bound}: the chunked "
                f"delta rule is built for log-decays in [{KDA_LOWER}, 0)")
        la = self.latent
        if la is not None and not linear and (
                la.q_lora_rank is None or la.qk_norm or la.head_gate):
            raise ValueError(
                "latent.q_lora_rank=None, qk_norm and head_gate are the "
                "'latent_attention' layers' of `layer_types`")

    def _check_experts(self):
        ex = self.experts
        for field, kinds in (("router", ROUTERS),
                             ("activation", tuple(ACTIVATIONS)),
                             ("router_input", ROUTER_INPUTS)):
            if getattr(ex, field) not in kinds:
                raise ValueError(f"unknown experts.{field} "
                                 f"{getattr(ex, field)!r}; expected one of "
                                 f"{kinds}")
        if ex.num_shared < 0 or ex.first_dense < 0:
            raise ValueError("experts.num_shared and experts.first_dense "
                             "count experts and blocks: 0 or more")
        if ex.shared_width is not None and (
                ex.shared_width < 1 or not ex.num_shared):
            raise ValueError(
                f"experts.shared_width {ex.shared_width}: the width of the "
                "shared expert, where num_shared says there is one")
        if self.single_sublayer and (
                ex.first_dense or ex.router_input != "ffn"):
            raise ValueError(
                "a model of 'feed_forward' blocks has no leading dense "
                "blocks to count (experts.first_dense would point at a "
                "mixer) and no block input but the feed-forward's own "
                "(experts.router_input='ffn')")
        if not (1 <= ex.topk_group <= ex.n_group) or (
                ex.num_experts % ex.n_group) or (
                    ex.n_group > 1 and ex.router != "sigmoid_noaux_tc"):
            raise ValueError(
                f"experts.n_group {ex.n_group} / topk_group {ex.topk_group}:"
                f" equal groups of the {ex.num_experts} experts, some of "
                "them kept, under the sigmoid router")
        if ex.weight_eps < 0 or (
                ex.weight_eps and ex.router != "sigmoid_noaux_tc"):
            raise ValueError(
                f"experts.weight_eps {ex.weight_eps}: 0 or more, added to "
                "the sum the sigmoid router's chosen scores are divided by")
        if ex.n_group > 1 and (
                ex.num_experts // ex.n_group < 2
                or ex.topk_group * (ex.num_experts // ex.n_group)
                < ex.top_k):
            raise ValueError(
                "experts.n_group: a group's score is the sum of its two "
                "best, and the kept groups must hold top_k experts")

    def _check_mixers(self):
        types, hy = tuple(self.layer_types), self.hybrid
        for i, kind in enumerate(types):
            if kind == "gmu" and "mamba" not in types[:i]:
                raise ValueError(f"block {i} is a gmu with no mamba block "
                                 "before it to read the memory of")
            if (kind == "cross_diff_attention"
                    and "diff_attention" not in types[:i]):
                raise ValueError(
                    f"block {i} is a cross_diff_attention with no "
                    "diff_attention block (full, not windowed) before it "
                    "to read keys and values of")
        if (self.attention, self.ffn, self.norm, self.mtp_depth, self.latent,
                self.experts, self.grouped) != (
                    "fused_qkv", "swiglu", "layernorm", 0, None, None, None):
            raise ValueError(
                "the SambaY mixers' blocks are built with ffn='swiglu', "
                "norm='layernorm', no MTP module, no `latent` or `experts` "
                "sizes and `attention` left at its default")
        heads, groups = self.num_heads, hy.kv_heads
        if heads % 2 or groups % 2 or heads % groups or (
                self.hidden_size % heads):
            raise ValueError(
                f"differential attention pairs adjacent heads: {heads} "
                f"query and {groups} key/value heads of "
                f"{self.hidden_size}/{heads} do not pair up")

    @staticmethod
    def small() -> "GPTConfig":
        return GPTConfig()

    @staticmethod
    def tiny() -> "GPTConfig":
        """For tests/dryruns."""
        return GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                         num_heads=4, max_position=512, dtype=jnp.float32)


def _norm(cfg: GPTConfig, name: str, x, dtype=jnp.float32):
    """The configuration's norm ``name`` of ``x``, computed in f32 and
    returned in ``dtype``, under the layer scope ``bf.block.norm`` (the
    cast with it: where XLA makes it a fusion's root, the fusion is the
    norm's)."""
    with jax.named_scope("bf.block.norm"):
        return _norm_module(cfg, name)(x).astype(dtype)


def _norm_module(cfg: GPTConfig, name: str, remat: bool = False, **parent):
    """The module of :func:`_norm`, for a caller that builds it under a
    ``parent`` of its choice and may ask for it rematerialised (a looped
    model's ``ln_f``, inside the scan over the rounds)."""
    norm = nn.RMSNorm if cfg.norm == "rmsnorm" else nn.LayerNorm
    if remat:
        norm = nn.remat(norm)
    return norm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name, **parent)


def _after(cfg: GPTConfig, name: str, y):
    """A sub-layer's output ``y`` on its way into the residual sum: through
    the norm ``name`` where the blocks are sandwiched, else as it is."""
    return _norm(cfg, name, y, cfg.dtype) if cfg.sandwich_norm else y


_LANES = 128     # of a TPU tile: a head this wide fills a matmul's output


class HeadDense(nn.Module):
    """``nn.Dense`` for a projection whose output (or, ``inward``, input)
    axis is split into heads, written so that the attention kernel's
    ``(B, H, T, D)`` operands cost as few passes over them as the chip
    allows (PERF.md, PR 33).

    It owns ``nn.Dense``'s two leaves under ``nn.Dense``'s names, shapes,
    dtypes and initialisers (``kernel (in, features)``, ``bias
    (features,)``), so a parameter tree does not see the difference, and
    applies them as ``nn.Dense`` does (operands in ``dtype``, the bias added
    after the dot).  Heads out: ``(..., T, in) -> (..., T, *heads)`` with
    ``features = parts * prod(heads)``, as a tuple of ``parts`` such tensors
    where a projection is fused (``q, k, v``).  Heads in: ``(..., T,
    *heads) -> (..., T, features)`` with ``in = prod(heads)``.  What it
    does depends on the head's width ``heads[-1]``, which is all that
    decided on the chip:

    - **at least a tile's 128 lanes** (latent attention: 192, 256, 128):
      the head axes are dimensions of the dot itself (the kernel reshaped to
      ``(in, *heads)`` or ``(*heads, features)``).  XLA lays a dot's output
      dimension out as its consumer wants it, so the ``reshape`` and
      ``transpose`` copies between projection and kernel go, and the dots
      keep their speed.
    - **narrower** (64): such a dot fills half of the matrix unit's output
      and runs at half its rate, which costs more than the copies it saves.
      Heads out, the 2-D matmul is instead written channel-major, ``(...,
      features, T)``, from where one transpose reaches the kernel's layout
      (after ``nn.Dense`` XLA takes two, a ``split`` and a scale pass); the
      barrier pins that layout (without it XLA folds the transpose away
      again where the batch is small).  Heads in, it is ``nn.Dense`` on
      the flattened heads.
    """

    features: int
    heads: Tuple[int, ...]
    inward: bool = False
    parts: int = 1       # heads out: this many tensors of ``heads`` each
    use_bias: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        heads, n = tuple(self.heads), len(self.heads)
        fan_in = math.prod(heads) if self.inward else x.shape[-1]
        kernel = self.param("kernel", nn.linear.default_kernel_init,
                            (fan_in, self.features), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,), jnp.float32) if (
                              self.use_bias) else None
        wide = heads[-1] >= _LANES
        # Reshapes come before the cast: the kernel's cotangent then goes
        # dot -> f32 -> reshape and XLA keeps the f32 accumulator (cast
        # first, the weight gradient is rounded to bf16 on its way back).
        contracted = 1
        if wide and self.inward:
            kernel = kernel.reshape(heads + (self.features,))
            contracted = n
        elif wide:
            kernel = kernel.reshape((fan_in, self.parts) + heads)
            bias = None if bias is None else bias.reshape(
                (self.parts,) + heads)
        elif self.inward:
            x = x.reshape(x.shape[:-n] + (fan_in,))
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                                  dtype=self.dtype)
        if wide or self.inward:
            y = jax.lax.dot_general(
                x, kernel, ((tuple(range(x.ndim - contracted, x.ndim)),
                             tuple(range(contracted))), ((), ())))
            y = y if bias is None else y + bias
            if self.inward:
                return y
            y = jnp.moveaxis(y, -n - 1, 0)             # the parts lead
        else:
            # the weights as the einsum's left operand: written the other
            # way round XLA compiles the program nn.Dense gives
            y = jnp.einsum("...td,dc->...ct", x, kernel)
            y = jax.lax.optimization_barrier(
                y if bias is None else y + bias[:, None])
            y = [jnp.moveaxis(p.reshape(p.shape[:-2] + heads + p.shape[-1:]),
                              -1, -n - 1)              # (..., T, *heads)
                 for p in jnp.split(y, self.parts, axis=-2)]
        return tuple(y) if self.parts > 1 else y[0]


def rotary(x, positions, theta: float, interleaved: bool = True):
    """Rotate pair ``i`` of the last axis by ``position * theta ** (-2i /
    width)``: the pairs are ``(0, 1), (2, 3), ...`` as stored
    (``interleaved``), or ``(i, i + width / 2)``, the two halves of the head
    (the half-split pairing).  ``x (B, T, H, R)``, ``positions (B or 1,
    T)``; computed in f32."""
    r = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angle)[:, :, None, :], jnp.sin(angle)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    if not interleaved:
        first, second = x32[..., :r // 2], x32[..., r // 2:]
        return jnp.concatenate([first * cos - second * sin,
                                first * sin + second * cos],
                               axis=-1).astype(x.dtype)
    pairs = x32.reshape(x.shape[:-1] + (r // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return turned.reshape(x.shape).astype(x.dtype)


def _heads(cfg: GPTConfig) -> int:
    """The heads this chip's ``kda`` and ``latent_attention`` layers hold."""
    return cfg.num_heads if cfg.heads_held is None else cfg.heads_held[1]


def _head_gate(a, gate):
    """``a (B, T, H, d)`` times ``sigmoid(gate (B, T, H))``, one scalar a
    head, in f32 and returned in ``gate``'s dtype."""
    return (a.astype(jnp.float32) * jax.nn.sigmoid(
        gate.astype(jnp.float32))[..., None]).astype(gate.dtype)


def _step_bias_init(floor=None):
    """Initialiser of a recurrence's step bias (Mamba's, Kimi Linear's):
    the inverse softplus of a step log-uniform in [1e-3, 1e-1], no less than
    ``floor`` where one is given."""
    def init(key, shape, dtype):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        if floor is not None:
            dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))      # softplus's inverse
    return init


def _a_log_init(key, shape, dtype):
    """``A_log = log U(1, 16)``, a head."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _uniform_within(bound):
    """Initialiser uniform in ``[-bound, bound]`` (a depthwise ``Conv1d``'s
    default at ``bound = taps ** -0.5``)."""
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


class LatentAttention(nn.Module):
    """Multi-head latent attention, as trained (keys and values are
    materialised; no matrix absorption): ``(B, T, D) -> (B, T, D)``.

    ``cq = RMSNorm(W_dq y)``, ``q = W_uq cq`` per head ``[q_nope; q_rope]``
    (or ``q = W_q y`` without the bottleneck: ``latent.q_lora_rank=None``);
    ``[ckv; k_rope] = W_dkv y``, ``[k_nope; v] = W_ukv RMSNorm(ckv)`` per
    head; with ``latent.qk_norm`` an RMSNorm over each head's whole query
    and whole key ``[k_nope; k_rope]``; rotary on ``q_rope`` and on
    ``k_rope`` (one a token, which every head shares, unless the key norm
    has scaled it head by head).  ``attn_fn`` sees ``q, k (B, T, H, nope +
    rope)`` and ``v (B, T, H, v_head_dim)`` and scales by the query's width.
    With ``latent.head_gate`` the heads' outputs are gated (:func:`_head_gate`)
    before ``W_o``.  No bias.  ``H`` is the heads held (:func:`_heads`).
    """

    cfg: GPTConfig

    @nn.compact
    def __call__(self, y, attn_fn: AttnFn, positions):
        cfg, la, h = self.cfg, self.cfg.latent, _heads(self.cfg)
        nope, rope = la.qk_nope_head_dim, la.qk_rope_head_dim
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)
        head_dense = functools.partial(HeadDense, use_bias=False,
                                       dtype=cfg.dtype)

        def rms(name):
            return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                              name=name)

        with jax.named_scope("bf.mla.project"):
            if la.q_lora_rank is None:
                q = head_dense(h * (nope + rope), (h, nope + rope),
                               name="q")(y)
            else:
                cq = rms("q_norm")(dense(la.q_lora_rank, name="q_down")(y))
                q = head_dense(h * (nope + rope), (h, nope + rope),
                               name="q_up")(cq.astype(cfg.dtype))
            ckv = dense(la.kv_lora_rank + rope, name="kv_down")(y)
            k_rope = ckv[..., None, la.kv_lora_rank:]        # (B, T, 1, rope)
            ckv = rms("kv_norm")(ckv[..., :la.kv_lora_rank])
            kv = head_dense(h * (nope + la.v_head_dim),
                            (h, nope + la.v_head_dim), name="kv_up")(
                                ckv.astype(cfg.dtype))
            if la.qk_norm:
                q = rms("q_head_norm")(q).astype(cfg.dtype)
            q = jnp.concatenate(
                [q[..., :nope], rotary(q[..., nope:], positions,
                                       la.rope_theta)], axis=-1)
            if not la.qk_norm:      # one turn a token serves every head
                k_rope = rotary(k_rope, positions, la.rope_theta)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope, kv.shape[:-1] + (rope,))], axis=-1)
            if la.qk_norm:          # the norm scales k_rope head by head
                k = rms("k_head_norm")(k).astype(cfg.dtype)
                k = jnp.concatenate(
                    [k[..., :nope],
                     rotary(k[..., nope:], positions, la.rope_theta)],
                    axis=-1)
        a = attn_fn(q, k, kv[..., nope:])
        with jax.named_scope("bf.mla.project"):
            if la.head_gate:
                a = _head_gate(a, dense(h, name="head_gate")(y))
            return head_dense(cfg.hidden_size, (h, la.v_head_dim),
                              inward=True, name="o")(a)


class KdaMixer(nn.Module):
    """Kimi Delta Attention (arXiv:2510.26692 section 3; the layer of
    ``fla/layers/kda.py``): ``(B, T, D) -> (B, T, D)``.  Per head of width
    ``d = kda.head_dim``:

    ``q~, k~, v = silu(conv(W_q y)), silu(conv(W_k y)), silu(conv(W_v y))``
    (causal depthwise convolutions of ``kda.conv`` taps, no bias);
    ``q = l2norm(q~) d^-1/2``, ``k = l2norm(k~)`` (f32 from the convolution
    through the norm between the projection's ``dtype`` in and out:
    :func:`bluefog_tpu.ops.short_conv.silu_short_conv`, on a TPU one kernel
    a direction and tensor where a head is whole lanes; elsewhere
    ``jax.numpy``; the six projections' pieces of ``y``'s cotangent are
    summed in one pass behind :func:`_sum_cotangents_once`, which also
    keeps a plain reference step's matmuls tiled as this step's: PERF.md
    section 6, PR 50); ``beta = sigmoid(W_b y)``
    a head; the log-decay a channel ``g = lower_bound sigmoid(exp(A_log_h)
    (W_f y + dt_bias))`` in f32; ``o = kda(q, k, v, g, beta)``
    (:func:`bluefog_tpu.ops.kda.kda`); output ``W_o [RMSNorm_head(o)
    sigmoid(W_g y)_h]``, the norm over a head's ``d`` with one scale shared
    by the heads, the gate one scalar a head.  No bias but ``dt_bias``.
    The heads are the ones held (:func:`_heads`).  Initialisers: Kimi
    Linear's for the recurrence, ``A_log = log U(1, 16)`` a head and
    ``dt_bias`` the inverse softplus of a step log-uniform in [1e-3, 1e-1];
    the taps uniform within ``conv ** -0.5``; flax's elsewhere."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, y):
        cfg, sizes, h = self.cfg, self.cfg.kda, _heads(self.cfg)
        d = sizes.head_dim
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)
        lead = y.shape[:-1]

        taps = _uniform_within(sizes.conv ** -0.5)

        with jax.named_scope("bf.kda.project"):
            y = _sum_cotangents_once(y)
            projected = [dense(h * d, name=name)(y) for name in "qkv"]
            decay = dense(h * d, name="f")(y)
            beta = jax.nn.sigmoid(dense(h, name="b")(y).astype(jnp.float32))
            gate = dense(h, name="head_gate")(y)
        with jax.named_scope("bf.kda.conv"):
            no_bias = jnp.zeros((h * d,), jnp.float32)
            norms = ((d, 1e-6, d ** -0.5), (d, 1e-6, 1.0), None)
            q, k, v = (silu_short_conv(
                x, self.param(f"{name}_conv", taps, (sizes.conv, h * d),
                              jnp.float32), no_bias,
                l2norm=l2norm).reshape(lead + (h, d))
                       for name, x, l2norm in zip("qkv", projected, norms))
        with jax.named_scope("bf.kda.project"):
            rate = jnp.exp(self.param("A_log", _a_log_init, (h,),
                                      jnp.float32))
            bias = self.param("dt_bias", _step_bias_init(), (h * d,),
                              jnp.float32)
            g = sizes.lower_bound * jax.nn.sigmoid(
                rate[:, None] * (decay.astype(jnp.float32) + bias).reshape(
                    lead + (h, d)))
        with jax.named_scope("bf.kda.scan"):
            o = kda(q, k, v, g, beta)
        with jax.named_scope("bf.kda.norm_gate"):
            o = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                           name="o_norm")(o)
            o = _head_gate(o, gate)
        with jax.named_scope("bf.kda.project"):
            out = dense(cfg.hidden_size, name="o")(o.reshape(lead + (h * d,)))
        return metrics_comm.count(out, [("bf_cconv_calls_total", 1.0)])


class GroupedQueryAttention(nn.Module):
    """Grouped-query attention without biases: ``(B, T, D) -> (B, T, D)``.

    ``q = W_q y`` in ``num_heads`` heads, ``k = W_k y`` and ``v = W_v y`` in
    ``grouped.kv_heads`` heads, all ``grouped.head_dim`` wide; query head
    ``h`` reads key/value head ``h // (num_heads / kv_heads)`` (the grouped
    heads of :func:`~bluefog_tpu.ops.ring_attention.local_attention`, which
    ``attn_fn`` is handed as they are).  With ``grouped.qk_norm``, ``q`` and
    ``k`` first go through an RMSNorm over each head's ``head_dim`` (scales
    ``q_norm``, ``k_norm``, one a side shared by the heads; ``norm_eps``;
    f32).  ``kind`` is the layer's type, **one of three** (what it sees,
    whether it turns ``q`` and ``k``):

    ==========================  ===========================  ===============
    ``full_attention``          every key ``s <= t``         turns nothing
                                                             (no positional
                                                             encoding)
    ``full_rotary_attention``   every key ``s <= t``         rotary
    ``window_rotary_attention`` the last ``window`` keys     rotary
    ==========================  ===========================  ===============

    The rotary is over the whole head, half-split pairs, ``rope_theta``.
    Scores scale by ``head_dim ** -0.5`` (``attn_fn``'s default for that
    width)."""

    cfg: GPTConfig
    kind: str

    @nn.compact
    def __call__(self, y, attn_fn: AttnFn, positions):
        cfg, gq = self.cfg, self.cfg.grouped
        windowed = self.kind == "window_rotary_attention"
        turned = windowed or self.kind == "full_rotary_attention"
        head_dense = functools.partial(HeadDense, use_bias=False,
                                       dtype=cfg.dtype)
        with jax.named_scope("bf.attn.project"):
            q = head_dense(cfg.num_heads * gq.head_dim,
                           (cfg.num_heads, gq.head_dim), name="q")(y)
            k = head_dense(gq.kv_heads * gq.head_dim,
                           (gq.kv_heads, gq.head_dim), name="k")(y)
            v = head_dense(gq.kv_heads * gq.head_dim,
                           (gq.kv_heads, gq.head_dim), name="v")(y)
            if gq.qk_norm:
                q, k = (nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                                   name=name)(x).astype(cfg.dtype)
                        for name, x in (("q_norm", q), ("k_norm", k)))
        if turned:
            with jax.named_scope("bf.attn.rotary"):
                q = rotary(q, positions, gq.rope_theta, interleaved=False)
                k = rotary(k, positions, gq.rope_theta, interleaved=False)
        a = attn_fn(q, k, v, **({"window": gq.window} if windowed else {}))
        a = metrics_comm.count(a, [
            ("bf_attn_window_calls_total", 1.0) if windowed
            else ("bf_attn_full_calls_total", 1.0)])
        with jax.named_scope("bf.attn.project"):
            return head_dense(cfg.hidden_size, (cfg.num_heads, gq.head_dim),
                              inward=True, name="o")(a)


def lambda_init(layer: int) -> float:
    """Differential attention's layer-dependent constant (arXiv:2410.05258
    section 2), from the published layer index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def causal_depthwise_conv(x, kernel, bias):
    """``out_t = sum_j kernel[j] * x_{t - (K - 1) + j} + bias`` a channel,
    with zeros before the sequence: ``x (B, T, C)``, ``kernel (K, C)``.  As
    ``K`` shifted multiply-adds, which XLA fuses into one pass."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[j] * padded[:, j:j + t] for j in range(taps)) + bias


class ShortConv(nn.Module):
    """LFM2's gated short convolution (``Lfm2ShortConv`` of the published
    modelling code): ``(B, T, D) -> (B, T, D)``, no bias, no activation.

    ``[b; c; z] = W_in y`` (three thirds of ``3 D``, in that order);
    ``s = b * z``; ``conv_t = sum_j k_j * s_{t - (taps - 1) + j}`` a channel
    with zeros before the sequence (``taps = short_conv.taps``); output
    ``W_out (c * conv)``.  The two gates and the taps are f32 between the
    projections' ``dtype``
    (:func:`bluefog_tpu.ops.short_conv.gated_short_conv`: on a TPU one
    kernel a direction, one pass over ``b``, ``z`` and ``c`` in and the
    gated result out; elsewhere ``jax.numpy``).  The taps start uniform
    within ``taps ** -0.5`` (a depthwise ``Conv1d``'s default), the
    projections at flax's."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, y):
        cfg, taps = self.cfg, self.cfg.short_conv.taps
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)

        with jax.named_scope("bf.sconv.project"):
            bcz = dense(3 * cfg.hidden_size, name="in_proj")(y)
        with jax.named_scope("bf.sconv.gate_conv"):
            kernel = self.param("conv_kernel", _uniform_within(taps ** -0.5),
                                (taps, cfg.hidden_size), jnp.float32)
            gated = gated_short_conv(bcz, kernel)
        with jax.named_scope("bf.sconv.project"):
            out = dense(cfg.hidden_size, name="out_proj")(gated)
        return metrics_comm.count(out, [("bf_sconv_calls_total", 1.0)])


class MambaMixer(nn.Module):
    """Mamba-1's mixer (arXiv:2312.00752 section 3.4): ``(B, T, D) ->``
    the output ``(B, T, D)`` and the memory ``m (B, T, d_inner)``, the scan's
    output before the gate, which a later gated memory unit reads.

    ``[xi; z] = W_in y``; ``x = silu(conv(xi) + b_c)``;
    ``[dr; B; C] = W_x x``; ``delta = softplus(W_dt dr + b_dt)``;
    ``m = selective_scan(x, delta, -exp(A_log), B, C, D)``; output
    ``W_out (m * silu(z))``.  ``delta``, ``A`` and the recurrence are f32.
    The initialisers are Mamba's published ones, which make the recurrence
    the dynamical system it was designed as: ``A_log = log(1 .. N)``,
    ``D = 1``, ``b_dt`` the inverse softplus of a ``delta`` log-uniform in
    [1e-3, 1e-1], ``W_dt`` uniform within ``dt_rank ** -0.5``."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, y):
        cfg, hy = self.cfg, self.cfg.hybrid
        inner, n, rank = hy.d_inner, hy.d_state, hy.dt_rank
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)

        within = _uniform_within

        with jax.named_scope("bf.ssm.project"):
            xi, z = jnp.split(dense(2 * inner, name="in_proj")(y), 2, axis=-1)
        with jax.named_scope("bf.ssm.conv"):
            taps = self.param("conv_kernel", within(hy.d_conv ** -0.5),
                              (hy.d_conv, inner), jnp.float32)
            bias = self.param("conv_bias", within(hy.d_conv ** -0.5),
                              (inner,), jnp.float32)
            x = nn.silu(causal_depthwise_conv(
                xi.astype(jnp.float32), taps, bias)).astype(cfg.dtype)
        with jax.named_scope("bf.ssm.project"):
            dbc = dense(rank + 2 * n, name="x_proj")(x)
            delta = nn.softplus(nn.Dense(
                inner, dtype=jnp.float32, name="dt_proj",
                kernel_init=within(rank ** -0.5),
                bias_init=_step_bias_init())(
                    dbc[..., :rank]))
        a_log = self.param(
            "A_log", lambda key, shape, dtype: jnp.broadcast_to(
                jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape),
            (inner, n), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (inner,), jnp.float32)
        with jax.named_scope("bf.ssm.scan"):
            m = selective_scan(x, delta, -jnp.exp(a_log),
                               dbc[..., rank:rank + n], dbc[..., rank + n:],
                               skip)
        with jax.named_scope("bf.ssm.project"):
            return dense(cfg.hidden_size, name="out_proj")(m * nn.silu(z)), m


@jax.custom_vjp
def _sum_cotangents_once(x):
    """``x``, with a fence on its cotangent: where several readers each hand
    back a piece (a Mamba-2 mixer's ``dz``, the convolution's three and ``d
    dt``; a KDA mixer's six projections of its input), the pieces are
    padded and added in one pass of their own and
    every matmul of the backward pass reads the sum.  Without it XLA fuses
    the padding and adding into each matmul that reads it, operand tile by
    operand tile (PERF.md section 6, PR 49: 17 ms a step in
    ``nemotron3nano.t8192.solo`` against 4 for the pass).  Forward it is
    ``x`` itself and compiles to nothing."""
    return x


_sum_cotangents_once.defvjp(
    lambda x: (x, None),
    lambda _, g: (jax.lax.optimization_barrier(g),))


class Mamba2Mixer(nn.Module):
    """Mamba-2's mixer (arXiv:2405.21060; the layer of the published
    ``nemotron_h`` modelling code): ``(B, T, D) -> (B, T, D)``.  With ``H``
    heads of ``P`` channels, inner width ``I = H P``, state ``N`` and ``G``
    groups (:class:`Mamba2Sizes`):

    ``[z (I); xBC (I + 2 G N); dt (H)] = W_in y``, in that order;
    ``xBC = silu(conv(xBC) + b_c)`` (causal depthwise, ``conv`` taps, f32
    between the projection's ``dtype`` in and out:
    :func:`bluefog_tpu.ops.short_conv.silu_short_conv`, on a TPU one kernel
    a direction and piece of the split, reading the projection's output
    where it lies, the pieces of its cotangent summed in one pass behind
    :func:`_sum_cotangents_once`; elsewhere ``jax.numpy``);
    ``[x (H, P); B (G, N); C (G, N)] = split(xBC)``; ``delta = softplus(dt +
    dt_bias)`` a head; ``o = ssd(x, delta, -exp(A_log), B, C, D)``
    (:func:`bluefog_tpu.ops.ssd.ssd`: the state decays by one scalar a head
    and token, head ``h`` reads group ``h // (H / G)``); ``o = g *
    GroupRMS(o * silu(z))``, the gate first and then the mean of squares
    over each group's ``I / G`` channels; output ``W_out o``.  No bias but
    the convolution's and ``dt_bias``.  ``delta``, ``A``, the recurrence's
    decay and state, the gate and the norm are f32.  Initialisers: Mamba-2's
    for the recurrence, ``A_log = log U(1, 16)`` a head, ``D = 1``,
    ``dt_bias`` the inverse softplus of a step log-uniform in [1e-3, 1e-1]
    floored at 1e-4; the taps and their bias uniform within ``conv **
    -0.5``; flax's elsewhere."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, y):
        cfg, sizes = self.cfg, self.cfg.mamba2
        h, p, n, g = sizes.heads, sizes.head_dim, sizes.state, sizes.groups
        inner = h * p
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)
        lead = y.shape[:-1]

        within = _uniform_within(sizes.conv ** -0.5)

        with jax.named_scope("bf.ssd.project"):
            zxbcdt = _sum_cotangents_once(
                dense(2 * inner + 2 * g * n + h, name="in_proj")(y))
            z = zxbcdt[..., :inner]
            delta = nn.softplus(
                zxbcdt[..., -h:].astype(jnp.float32)
                + self.param("dt_bias", _step_bias_init(floor=1e-4), (h,),
                             jnp.float32))
            a = -jnp.exp(self.param("A_log", _a_log_init, (h,), jnp.float32))
            skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        with jax.named_scope("bf.ssd.conv"):
            taps = self.param("conv_kernel", within,
                              (sizes.conv, inner + 2 * g * n), jnp.float32)
            bias = self.param("conv_bias", within, (inner + 2 * g * n,),
                              jnp.float32)
            x, b, c = silu_short_conv(zxbcdt, taps, bias, offset=inner,
                                      pieces=(inner, g * n, g * n))
        with jax.named_scope("bf.ssd.scan"):
            o = ssd(x.reshape(lead + (h, p)), delta, a,
                    b.reshape(lead + (g, n)), c.reshape(lead + (g, n)), skip)
        with jax.named_scope("bf.ssd.norm_gate"):
            gated = (o.reshape(lead + (inner,)).astype(jnp.float32)
                     * nn.silu(z.astype(jnp.float32)))
            scale = self.param("norm_scale", nn.initializers.ones, (inner,),
                               jnp.float32)
            grouped = gated.reshape(lead + (g, inner // g))
            grouped = grouped * jax.lax.rsqrt(
                jnp.mean(grouped * grouped, axis=-1, keepdims=True)
                + cfg.norm_eps)
            o = (grouped.reshape(lead + (inner,)) * scale).astype(cfg.dtype)
        with jax.named_scope("bf.ssd.project"):
            out = dense(cfg.hidden_size, name="out_proj")(o)
        return metrics_comm.count(out, [("bf_ssd_calls_total", 1.0),
                                        ("bf_cconv_calls_total", 1.0)])


class GatedMemoryUnit(nn.Module):
    """``W_out (m * silu(W_in y))`` (SambaY, arXiv:2507.06607 section 2):
    the memory ``m`` of an earlier Mamba block, gated element by element by
    this layer's own input.  No bias."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, y, memory):
        cfg = self.cfg
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)
        with jax.named_scope("bf.gmu.gate"):
            gated = memory * nn.silu(
                dense(cfg.hybrid.d_inner, name="in_proj")(y))
            return dense(cfg.hidden_size, name="out_proj")(gated)


class DiffAttention(nn.Module):
    """Differential attention (arXiv:2410.05258) over grouped key/value
    heads: ``(B, T, D) ->`` the output and the ``(k, v)`` it attended over.

    Query pair ``p`` is heads ``(2p, 2p + 1)``, key pair ``g = p // (P / G)``
    likewise, the pair's value its two value heads side by side;
    ``o_p = A_1 V_g - lam A_2 V_g`` with ``A_j`` the softmax map of the
    pair's ``j``-th query and key head, ``lam = exp(lq1 . lk1) -
    exp(lq2 . lk2) + lam_init``; ``o_p <- RMSNorm(o_p) (1 - lam_init)``,
    one scale shared by the pairs; then the biased output projection.  With
    ``keys_values`` (another layer's ``(k, v)``) only the query is this
    layer's: cross attention.

    For the kernel the ``2P`` maps are ``2P`` plain heads with ``D``-wide
    queries and keys and ``2D``-wide values, in the order ``(g, j, r)`` (key
    pair, map, query pair within the group), so that head ``h`` reads key
    head ``h // (P / G)`` and value pair ``h // (2P / G)``: the grouped
    heads of :func:`~bluefog_tpu.ops.ring_attention.local_attention`.
    ``k (B, T, 2G, D)`` and ``v (B, T, G, 2D)`` are handed on as they are.
    """

    cfg: GPTConfig
    layer: int                      # published index, for lambda_init
    window: Optional[int] = None
    cross: bool = False

    @nn.compact
    def __call__(self, y, attn_fn, keys_values=None):
        cfg = self.cfg
        heads, groups = cfg.num_heads, cfg.hybrid.kv_heads
        dim, width = cfg.hidden_size // cfg.num_heads, cfg.hidden_size
        share = heads // groups         # query pairs a key pair
        lead = y.shape[:-1]
        with jax.named_scope("bf.attn.project"):
            if self.cross:
                q = nn.Dense(width, dtype=cfg.dtype, name="q")(y)
                k, v = keys_values
            else:
                qkv = nn.Dense(width + 2 * groups * dim, dtype=cfg.dtype,
                               name="qkv")(y)
                q = qkv[..., :width]
                k = qkv[..., width:width + groups * dim].reshape(
                    lead + (groups, dim))
                v = qkv[..., width + groups * dim:].reshape(
                    lead + (groups // 2, 2 * dim))
            # (g, r, j) as projected -> (g, j, r) for the kernel
            q = q.reshape(lead + (groups // 2, share, 2, dim))
            q = jnp.swapaxes(q, -3, -2).reshape(lead + (heads, dim))
        mask = {} if self.window is None else {"window": self.window}
        a = attn_fn(q, k, v, **mask)
        a = metrics_comm.count(a, [
            ("bf_attn_full_calls_total", 1.0) if self.window is None
            else ("bf_attn_window_calls_total", 1.0)])
        lam = {name: self.param(name, nn.initializers.normal(0.1), (dim,),
                                jnp.float32)
               for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                            "lambda_k2")}
        with jax.named_scope("bf.attn.diff"):
            init = lambda_init(self.layer)
            weight = (jnp.exp(jnp.sum(lam["lambda_q1"] * lam["lambda_k1"]))
                      - jnp.exp(jnp.sum(lam["lambda_q2"] * lam["lambda_k2"]))
                      + init)
            a = a.astype(jnp.float32).reshape(
                lead + (groups // 2, 2, share, 2 * dim))
            o = a[..., 0, :, :] - weight * a[..., 1, :, :]
            o = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                           name="subln")(o) * (1.0 - init)
            o = o.reshape(lead + (width,)).astype(cfg.dtype)
        with jax.named_scope("bf.attn.project"):
            return nn.Dense(width, dtype=cfg.dtype, name="out")(o), (k, v)


class GatedMLP(nn.Module):
    """``down(silu(gate(y)) * up(y))``, no bias."""

    width: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, y):
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        gated = nn.silu(dense(self.width, name="gate")(y))
        return dense(y.shape[-1], name="down")(
            gated * dense(self.width, name="up")(y))


class UngatedMLP(nn.Module):
    """``down(activation(up(y)))``, no bias: the shared expert of a layer
    whose experts are ungated (``experts.gated=False``)."""

    width: int
    dtype: jnp.dtype
    activation: str

    @nn.compact
    def __call__(self, y):
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        hidden = ACTIVATIONS[self.activation](dense(self.width, name="up")(y))
        return dense(y.shape[-1], name="down")(hidden)


def _route(ex: ExpertSizes, flat, router, bias):
    """``(idx, weights)`` of ``cfg.experts``' router over ``flat (T, D)``.
    A plain function, as :func:`_mix`."""
    if ex.router == "softmax_topk":
        idx, weights = softmax_topk_router(flat, router, top_k=ex.top_k)
    else:
        idx, weights = sigmoid_topk_router(
            flat, router, bias.value, top_k=ex.top_k, scale=ex.scale,
            n_group=ex.n_group, topk_group=ex.topk_group,
            eps=ex.weight_eps)
    if not ex.train_router:
        weights = jax.lax.stop_gradient(weights)
    return idx, weights


class RoutedFFN(nn.Module):
    """This chip's share of the routed experts
    (:func:`bluefog_tpu.ops.moe.routed_experts`: dropless, grouped matmuls
    over the held experts) plus, where ``experts.num_shared`` is not 0, a
    shared expert every token takes.  The parameters hold the held experts
    only (``w_gate``, ``w_up``, ``w_down``; ungated experts have no
    ``w_gate``, and their shared expert is an :class:`UngatedMLP`); the
    router scores all of them.  The sigmoid router's selection
    bias is a buffer (collection ``buffers``, no gradient); the softmax
    router has none, and a layer without a shared expert no ``shared``
    module.  The routing record is sown into the collection ``moe_metrics``.

    ``__call__(y)`` routes on ``y`` itself.  Where the router reads the
    block's input instead (``experts.router_input == "block"``) the block
    asks for ``routing = route(block_input)`` before its attention and hands
    it to ``__call__(y, routing)`` after."""

    cfg: GPTConfig

    def setup(self):
        cfg, ex = self.cfg, self.cfg.experts
        d, count = cfg.hidden_size, ex.held[1]
        per_expert = nn.initializers.lecun_normal(
            in_axis=1, out_axis=2, batch_axis=(0,))
        self.router = self.param("router", nn.initializers.lecun_normal(),
                                 (d, ex.num_experts), jnp.float32)
        self.bias = self.variable(
            "buffers", "selection_bias", jnp.zeros, (ex.num_experts,),
            jnp.float32) if ex.router == "sigmoid_noaux_tc" else None
        self.w_gate = self.param("w_gate", per_expert, (count, d, ex.width),
                                 jnp.float32) if ex.gated else None
        self.w_up = self.param("w_up", per_expert, (count, d, ex.width),
                               jnp.float32)
        self.w_down = self.param("w_down", per_expert, (count, ex.width, d),
                                 jnp.float32)
        shared_width = ex.shared_width or ex.num_shared * ex.width
        if not ex.num_shared:
            self.shared = None
        elif ex.gated:
            self.shared = GatedMLP(shared_width, cfg.dtype)
        else:
            self.shared = UngatedMLP(shared_width, cfg.dtype, ex.activation)

    def route(self, y):
        """``(idx, weights)`` over ``y (B, T, D)``'s tokens, flattened."""
        return _route(self.cfg.experts, y.reshape(-1, y.shape[-1]),
                      self.router, self.bias)

    def __call__(self, y, routing=None):
        ex = self.cfg.experts
        flat = y.reshape(-1, y.shape[-1])
        idx, weights = (_route(ex, flat, self.router, self.bias)
                        if routing is None else routing)
        routed, record = routed_experts(
            flat, idx, weights, self.w_gate, self.w_up, self.w_down,
            num_experts=ex.num_experts, held=ex.held,
            activation=ex.activation)
        for name, value in record.items():
            self.sow("moe_metrics", name, value)
        routed = routed.reshape(y.shape)
        if self.shared is None:
            return routed
        with jax.named_scope("bf.mlp.dense"):
            return self.shared(y) + routed


def _mix(block, y, attn_fn, carried):
    """The block's token mixer (``block.mixer``) over the normed input, and
    what it leaves for later blocks.  A plain function: flax puts a method's
    name into the name stack, and so into every ``op_name`` under it."""
    cfg = block.cfg
    memory, keys, values = carried
    if block.mixer == "mamba":
        a, memory = MambaMixer(cfg, name="mamba")(y)
    elif block.mixer == "gmu":
        a = GatedMemoryUnit(cfg, name="gmu")(y, memory)
    elif block.mixer == "cross_diff_attention":
        a, _ = DiffAttention(cfg, block.layer, cross=True, name="attn")(
            y, attn_fn, (keys, values))
    else:
        windowed = block.mixer == "diff_attention_window"
        a, kv = DiffAttention(
            cfg, block.layer, name="attn",
            window=cfg.hybrid.window if windowed else None)(y, attn_fn)
        if not windowed:
            keys, values = kv
    return a, (memory, keys, values)


def _early_routing(block, y):
    """The expert layer's module and, where its router reads the block's
    normed input ``y``, the routing made from it: before the attention."""
    cfg = block.cfg
    if block.mlp is not None or (block.ffn or cfg.ffn) != ROUTED:
        return None, None
    moe = RoutedFFN(cfg, name="moe")
    return moe, (moe.route(y) if cfg.experts.router_input == "block"
                 else None)


def _feed_forward(block, x, moe=None, routing=None):
    """``x + FFN(norm(x))`` of the block's feed-forward kind; ``moe`` and
    ``routing`` as :func:`_early_routing` gave them."""
    cfg = block.cfg
    y = _norm(cfg, "ln2", x, cfg.dtype)
    ffn = block.ffn or cfg.ffn
    if block.mlp is not None:
        return x + _after(cfg, "ln2_post", block.mlp()(y))
    if ffn == ROUTED:
        return x + _after(cfg, "ln2_post", moe(y, routing))
    width = cfg.ffn_width or cfg.mlp_ratio * cfg.hidden_size
    with jax.named_scope("bf.mlp.dense"):
        if ffn == "swiglu":
            y = GatedMLP(width, cfg.dtype, name="mlp")(y)
        else:
            y = nn.Dense(width, dtype=cfg.dtype, name="up")(y)
            y = nn.gelu(y)
            y = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="down")(y)
    return x + _after(cfg, "ln2_post", y)


class Block(nn.Module):
    """Pre-norm attention + feed-forward residual block, assembled from the
    configuration's kinds (module docstring); with ``cfg.sandwich_norm`` each
    sub-layer's output goes through a norm of its own (``ln1_post``,
    ``ln2_post``) before the residual sum.  A looped model
    (``cfg.rounds > 1``) calls one block once a round, on the same leaves.

    ``mlp`` is a pluggable sublayer factory ``() -> nn.Module`` that replaces
    the configured feed-forward kind: ``mlp()`` maps ``(B, T, D) -> (B, T,
    D)`` (the MoE variant of models/moe.py injects a Switch-MoE FFN here
    instead of duplicating the attention trunk).  ``ffn`` overrides
    ``cfg.ffn`` for this block (the leading dense blocks of an expert model).

    ``mixer`` is the block's entry of ``cfg.layer_types``.  One of
    ``ATTENTION_LAYERS`` says what the grouped-query attention attends over
    and whether it turns its keys; ``short_conv`` puts :class:`ShortConv`
    (parameters ``conv``) and ``mamba2`` :class:`Mamba2Mixer` (parameters
    ``mixer``) in the attention's place.  **In a model of single-sub-layer
    blocks** (``cfg.single_sublayer``: ``layer_types`` names a
    ``feed_forward`` block) a block builds one norm and one sub-layer: a
    ``feed_forward`` block is ``x + FFN(ln2(x))`` and every other block
    ``x + mixer(ln1(x))``; still this one class.  One of ``MIXERS`` replaces the attention
    with that token mixer; such a block takes and returns ``carried =
    (memory, keys, values)`` beside ``x``: what the last Mamba block and the
    last full
    differential-attention block left for the gated memory units and the
    cross-attention layers after them.  Under ``nn.remat`` they are a
    block's outputs and the next blocks' inputs, so they are saved and not
    recomputed, and a reader's gradient flows back into the block that made
    them.
    """

    cfg: GPTConfig
    mlp: Optional[Callable[[], nn.Module]] = None
    ffn: Optional[str] = None
    mixer: Optional[str] = None
    layer: int = 0                   # published index (differential lambda)

    @nn.compact
    @startup.spanned("bf.setup.trace.block",
                     lambda block: block.mixer or block.cfg.attention)
    def __call__(self, x, attn_fn: AttnFn, positions=None, carried=None):
        cfg = self.cfg
        if self.mixer == FEED_FORWARD:
            return _feed_forward(self, x, *_early_routing(self, None))
        y = _norm(cfg, "ln1", x, cfg.dtype)
        if self.mixer in MIXERS:
            a, carried = _mix(self, y, attn_fn, carried)
            return _feed_forward(self, x + _after(cfg, "ln1_post", a)), carried
        moe, routing = (None, None) if cfg.single_sublayer else (
            _early_routing(self, y))
        if self.mixer == "kda":
            a = KdaMixer(cfg, name="attn")(y)
        elif self.mixer == "short_conv":
            a = ShortConv(cfg, name="conv")(y)
        elif self.mixer == "mamba2":
            a = Mamba2Mixer(cfg, name="mixer")(y)
        elif cfg.attention == "latent":
            a = LatentAttention(cfg, name="attn")(y, attn_fn, positions)
        elif cfg.attention == "grouped_query":
            a = GroupedQueryAttention(cfg, self.mixer, name="attn")(
                y, attn_fn, positions)
        else:
            heads = (cfg.num_heads, cfg.hidden_size // cfg.num_heads)
            with jax.named_scope("bf.attn.project"):
                q, k, v = HeadDense(3 * cfg.hidden_size, heads, parts=3,
                                    dtype=cfg.dtype, name="qkv")(y)
            a = attn_fn(q, k, v)
            with jax.named_scope("bf.attn.project"):
                a = HeadDense(cfg.hidden_size, heads, inward=True,
                              dtype=cfg.dtype, name="proj")(a)
        a = _after(cfg, "ln1_post", a)
        if cfg.single_sublayer:
            return x + a
        return _feed_forward(self, x + a, moe, routing)


def _run_rounds(model, x, make_blocks, attn_fn, positions, logits_of):
    """``cfg.rounds`` passes of the blocks over ``x``, the final norm once a
    round: ``(every round's logits, the gate's logits (rounds, B, T))``.
    ``make_blocks(parent=m)`` builds the stack under the module ``m``.

    The rounds are a ``lax.scan`` with the leaves broadcast (``nn.scan`` over
    a function of ``model``, so the blocks and ``ln_f`` keep their places in
    the parameter tree): XLA compiles one round's passes and runs them
    ``rounds`` times, where the unrolled loop compiles every pass of every
    round: four times the code, which no compile cache held (PERF.md
    section 6, PR 51).  A trace shows the loop's body ``rounds`` times a
    pass under ``bf.loop.round``; ``exit_<r>`` tells the exits apart."""
    cfg = model.cfg

    def one_round(mdl, x, _):
        # no callback inside the loop: flax's scan partial-evaluates its
        # body, which a rematerialised block's effects do not survive, so
        # the blocks' own counters are silent here and the loop's is
        # stamped once, after it
        with metrics_comm.suppress_comm_metrics(), jax.named_scope(
                "bf.loop.round"):
            for block in make_blocks(parent=mdl):
                x = block(x, attn_fn, positions)
            with jax.named_scope("bf.block.norm"):
                # f32: the exit's hidden state, and the next round's input;
                # under remat its f32 input is recomputed, not saved a round
                h = _norm_module(cfg, "ln_f", cfg.remat, parent=mdl)(x)
        return h.astype(cfg.dtype), h

    metrics_comm.set("bf_loop_rounds", cfg.rounds)
    _, hidden = nn.scan(one_round, variable_broadcast="params",
                        split_rngs={"params": False}, length=cfg.rounds)(
                            model, x, None)
    hidden = metrics_comm.count(hidden, [(
        "bf_loop_block_calls_total", float(cfg.rounds * cfg.num_layers))])
    with jax.named_scope("bf.loop.exit"):
        gates = nn.Dense(1, dtype=jnp.float32, precision="highest",
                         name="exit_gate")(hidden)[..., 0]
    exits = []
    for r in range(cfg.rounds):
        with jax.named_scope(f"exit_{r + 1}"):
            exits.append(logits_of(hidden[r]))
    return tuple(exits), gates


class TransformerLM(nn.Module):
    """Tokens → logits.  ``attn_fn(q, k, v) -> out`` defaults to full causal
    attention; inject a sequence-parallel attention inside ``shard_map`` and
    pass this rank's global ``position_offset``.  (A windowed block calls
    ``attn_fn(q, k, v, window=w)``, and the differential and the
    grouped-query blocks hand it grouped key/value heads: an injected
    ``attn_fn`` has to take both.)  ``mlp`` (a sublayer factory,
    see :class:`Block`) swaps every block's MLP — e.g. for Switch-MoE.

    With ``cfg.mtp_depth == 1`` and ``next_tokens`` (the tokens one place
    on, ``t_{i+1}``) it returns ``(logits, mtp_logits)``: the
    multi-token-prediction module of DeepSeek-V3 (arXiv:2412.19437 §2.2),
    ``h'_i = M [norm(Emb(t_{i+1})); norm(h_i)]`` with ``h_i`` the trunk's
    output before its final norm, one more block, and the trunk's own
    embedding and head (one leaf each, used twice); ``mtp_logits`` predicts
    ``t_{i+2}``.  ``head=False`` is :func:`next_token_loss`'s: the normed
    hidden states the head would read, in place of each logits (an ``init``
    runs with the head, which makes an untied head's leaf).

    With ``cfg.rounds > 1`` the blocks run that many times over the same
    leaves, ``x_r = ln_f(blocks(x_{r-1}))`` (:func:`_run_rounds`), and the
    result is ``(logits of every round, gate logits (rounds, B, T))``: the gate
    ``w_g . x_r + b_g`` in f32, whose sigmoids :func:`exit_distribution`
    turns into the probability of leaving after each round."""

    cfg: GPTConfig
    mlp: Optional[Callable[[], nn.Module]] = None

    @nn.compact
    def __call__(self, tokens, *, attn_fn: Optional[AttnFn] = None,
                 position_offset=0, positions=None, next_tokens=None,
                 head: bool = True):
        cfg = self.cfg
        if attn_fn is None:
            # the model layer is the perf path: opt into the fused TPU flash
            # kernel whenever eligible (parity: tests/test_flash_attention.py)
            attn_fn = lambda q, k, v, **mask: local_attention(
                q, k, v, causal=True, backend="auto", **mask)
        if positions is None:
            positions = position_offset + jnp.arange(tokens.shape[1])[None, :]
        # else: explicit per-token global positions — required by layouts
        # whose local block is not contiguous (e.g. the zigzag causal ring,
        # where a rank holds a front chunk and its mirrored back chunk)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         name="tok")
        with jax.named_scope("bf.embed.lookup"):
            x = take_rows(embed.embedding, tokens, cfg.dtype)
            if cfg.position == "learned":
                x = x + nn.Embed(cfg.max_position, cfg.hidden_size,
                                 dtype=cfg.dtype, name="pos")(positions)
        block_cls = nn.remat(Block, static_argnums=(2,)) if cfg.remat else Block
        dense_blocks = cfg.experts.first_dense if cfg.experts else 0
        carried = (None, None, None)     # memory, keys, values
        if cfg.hybrid is not None:
            for i in range(cfg.num_layers):
                x, carried = block_cls(
                    cfg, mixer=cfg.layer_types[i],
                    layer=cfg.hybrid.first_layer + i, name=f"block_{i}")(
                        x, attn_fn, positions, carried)

        def make_blocks(**parent):
            # a looped model's rounds build them under the scan's module:
            # the same names, so the same leaves every round
            return [block_cls(cfg, mlp=self.mlp, **parent,
                              ffn="swiglu" if i < dense_blocks else None,
                              mixer=cfg.layer_types[i] if cfg.layer_types
                              else None, name=f"block_{i}")
                    for i in range(cfg.num_layers)]
        if cfg.tie_head:
            def project(h):    # f32 logits from the f32 leaf, as lm_head's
                return jnp.einsum("...d,vd->...v", h, embed.embedding)
        else:
            project = nn.Dense(cfg.vocab_size, dtype=jnp.float32,
                               use_bias=False, name="lm_head")

        def logits_of(h):
            if not head:     # next_token_loss's: the head goes with the loss
                return h
            with jax.named_scope("bf.head.logits"):
                return project(h)

        if cfg.rounds > 1:
            return _run_rounds(self, x, make_blocks, attn_fn, positions,
                               logits_of)
        if cfg.hybrid is None:
            for block in make_blocks():
                x = block(x, attn_fn, positions)
        logits = logits_of(_norm(cfg, "ln_f", x))
        if next_tokens is None:
            return logits
        if not cfg.mtp_depth:
            raise ValueError("next_tokens needs cfg.mtp_depth == 1")
        with jax.named_scope("bf.embed.mtp_merge"):
            e = take_rows(embed.embedding, next_tokens, cfg.dtype)
        e, h = _norm(cfg, "mtp_enorm", e), _norm(cfg, "mtp_hnorm", x)
        with jax.named_scope("bf.embed.mtp_merge"):
            merged = jnp.concatenate([e, h], axis=-1).astype(cfg.dtype)
            z = nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                         name="mtp_proj")(merged)
        z = block_cls(cfg, mlp=self.mlp, name="mtp_block")(
            z, attn_fn, positions)
        return logits, logits_of(_norm(cfg, "mtp_norm", z))


def exit_distribution(gate_logits):
    """``log p (R, ...)`` from the exit gate's logits ``(R, ...)``: with
    ``g_r = sigmoid(l_r)``, ``p_r = g_r prod_{j<r} (1 - g_j)`` for ``r < R``
    and ``p_R = prod_{j<R} (1 - g_j)`` (the last round takes what is left;
    its own gate is not read), a distribution over the exits a token.  In
    logs, so that a gate far from 0 gives a small ``p`` and no ``nan``."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits[:-1]), axis=0)
    return jnp.concatenate([
        jax.nn.log_sigmoid(gate_logits[:1]),
        jax.nn.log_sigmoid(gate_logits[1:-1]) + stay[:-1], stay[-1:]])


def next_token_loss(model: TransformerLM, params, model_state, tokens, *,
                    mtp_weight: float = 0.0, exit_entropy_weight: float = 0.0,
                    attn_fn: Optional[AttnFn] = None):
    """The training loss of ``tokens (B, T + 1 + mtp_depth)``: the mean over
    the ``B * T`` positions of the cross entropy of the main head against
    ``t_{i+1}`` plus, with a multi-token-prediction module, ``mtp_weight``
    times that of the module's head against ``t_{i+2}``; logits in f32.
    ``model_state`` holds the non-parameter collections (``buffers``).

    With an exit gate (``cfg.exit_gate``; Ouro, arXiv:2510.25741 section 3)
    it is the expected loss over the exits less the entropy of where a token
    leaves: ``mean_i [sum_r p_r(i) CE_r(i) - exit_entropy_weight H(p(i))]``,
    ``p`` :func:`exit_distribution` of the gate's logits, ``CE_r`` the cross
    entropy of round ``r``'s logits.  ``p`` is a function of the parameters:
    each exit's head call takes ``p_r / (B T)`` as its rows' weights and
    hands the gate ``CE_r`` as their gradient.

    No whole ``(B, T, V)`` logits and no gradient of them are made: the
    model hands back its normed hidden states and ``ops/head_loss.py`` takes
    the head's leaf (``lm_head/kernel``, or the tied ``tok/embedding``)
    through the matmul, the cross entropy and both of the head's gradients
    a chunk of token rows at a time.  The MTP pair shares the leaf, as a
    looped model's exits do: a call each, whose gradients of it add."""
    cfg = model.cfg
    depth = cfg.mtp_depth
    t = tokens.shape[1] - 1 - depth
    variables = {"params": params, **model_state}
    leaf = (params["tok"]["embedding"] if cfg.tie_head
            else params["lm_head"]["kernel"])

    def cross_entropy(h, targets, site, weights=None):
        return head_loss(h, leaf, targets, tied=cfg.tie_head, site=site,
                         weights=weights)

    if cfg.exit_gate:
        exits, gate_logits = model.apply(variables, tokens[:, :t],
                                         attn_fn=attn_fn, head=False)
        with jax.named_scope("bf.loop.exit"):
            log_p = exit_distribution(gate_logits)
            p = jnp.exp(log_p)
            entropy = -jnp.sum(p * log_p, axis=0).mean()
            # read by the gauges alone: outside the differentiation, whose
            # tracers a callback's operands may not be
            mass = jax.lax.stop_gradient(p).mean(
                axis=tuple(range(1, p.ndim)))
            weights = p / p[0].size          # the mean over the positions
            loss = -exit_entropy_weight * entropy
        for r, h in enumerate(exits, 1):
            with jax.named_scope(f"exit_{r}"):
                loss = loss + cross_entropy(h, tokens[:, 1:], f"exit_{r}",
                                            weights[r - 1])
            loss = metrics_comm.gauge(loss, [("bf_loop_exit_mass",
                                              mass[r - 1])], {"round": r})
        return metrics_comm.gauge(loss, [(
            "bf_loop_expected_rounds",
            jnp.sum(mass * jnp.arange(1, len(exits) + 1)))])
    if not depth:
        h = model.apply(variables, tokens[:, :t], attn_fn=attn_fn, head=False)
        return cross_entropy(h, tokens[:, 1:], "main")
    h, mtp_h = model.apply(
        variables, tokens[:, :t], attn_fn=attn_fn,
        next_tokens=tokens[:, 1:t + 1], head=False)
    return (cross_entropy(h, tokens[:, 1:t + 1], "main")
            + mtp_weight * cross_entropy(mtp_h, tokens[:, 2:], "mtp"))
