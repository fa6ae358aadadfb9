"""Mixture-of-experts routing + expert parallelism (GShard/Switch style).

No counterpart exists in the reference (SURVEY.md §2.3: EP absent — Bluefog
predates MoE).  The TPU build adds it as the fourth parallelism axis: experts
are sharded over an ``'ep'`` mesh axis and tokens reach their expert via a
pair of ``lax.all_to_all`` hops — the canonical TPU MoE dataflow (dense
einsum dispatch, static capacity, no dynamic shapes, everything MXU-tiled).

Pieces:

- :func:`switch_router` — top-1 (Switch) routing with a static per-shard
  capacity: returns dense dispatch/combine tensors.
- :func:`expert_parallel_ffn` — dispatch → all_to_all → local expert FFNs →
  reverse all_to_all → combine, inside ``shard_map``.
- :func:`sigmoid_topk_router`, :func:`softmax_topk_router` and
  :func:`routed_experts` — the dropless layer for many small experts: a
  router over all the experts (sigmoid scores with a selection bias, or a
  softmax over the chosen logits), top-k of them, and this chip's share of
  the result over the experts
  it is told it holds: sort by expert, then passes of a row buffer sized
  from the held share (gather the rows, grouped matmuls over the held
  groups, then the held rows added to their tokens', weighted, in f32: in
  VMEM by a Pallas kernel on a TPU, by scatter-add elsewhere) until the
  held rows are done.  No capacity, no ``(T, E, C)`` tensor, no drop.
  The one-hot routers above stay for the ``all_to_all`` path, which needs
  the static per-expert capacity they provide.

Gradient convention: normalize the per-rank loss by the GLOBAL token count
(``local_sum / total_tokens``) so the per-rank loss seeds sum to the true
global objective.  Then raw ``jax.grad`` inside ``shard_map`` is exact for
the **ep-sharded expert parameters** (the ``all_to_all`` transposes route
cotangents back without scaling).  **Replicated parameters** (router,
embeddings, attention, …) receive only the local tokens' contribution on
each rank — ``lax.psum`` their grads over the ep axis before the optimizer
update, or the nominally replicated copies silently diverge (see
tests/test_moe.py::test_expert_parallel_grads_match_reference and the
``gr = lax.psum(gr, "ep")`` step in ``__graft_entry__.dryrun_multichip``).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.metrics import comm as metrics_comm
from bluefog_tpu.ops import row_sums
from bluefog_tpu.tracing import startup

__all__ = [
    "RouterOutput",
    "switch_router",
    "top2_router",
    "get_router",
    "expert_parallel_ffn",
    "moe_ffn_reference",
    "sigmoid_topk_router",
    "softmax_topk_router",
    "routed_experts",
]


class RouterOutput(NamedTuple):
    """dispatch/combine: ``(T, E, C)``; aux: scalar load-balance loss;
    metrics: non-differentiated accounting dict —

    - ``dropped_frac``: fraction of routing ASSIGNMENTS (token-choice
      pairs; a top-2 token makes two) past expert capacity, hence dropped;
    - ``fully_dropped_frac``: fraction of TOKENS with every assignment
      dropped (the residual connection alone carries them);
    - ``expert_load``: ``(E,)`` fraction of assignments per expert.
    """

    dispatch: jnp.ndarray
    combine: jnp.ndarray
    aux: jnp.ndarray
    metrics: dict


def _router_probs(x, router_kernel, noise_rng, noise_scale):
    """Shared preamble: f32 logits (+ optional exploration noise) -> probs."""
    logits = x.astype(jnp.float32) @ router_kernel.astype(jnp.float32)
    if noise_rng is not None and noise_scale > 0:
        logits = logits + noise_scale * jax.random.normal(noise_rng,
                                                          logits.shape)
    return jax.nn.softmax(logits, axis=-1)


def _assign_slots(onehot, capacity: int, base=0.0):
    """Queue one routing choice into expert slots.

    ``base`` (scalar or ``(1, E)``) offsets each expert's queue start —
    top-2's second choices pass the expert's first-choice count so they
    queue behind ALL first choices.  Returns ``(keep, slot)``: the
    surviving ``(T, E)`` mask and the ``(T, E, C)`` dispatch one-hots.
    """
    pos = (base + jnp.cumsum(onehot, axis=0) - 1.0) * onehot
    keep = (pos < capacity) * onehot
    slot = keep[..., None] * jax.nn.one_hot(pos.astype(jnp.int32), capacity)
    return keep, slot


def _router_metrics(assigned, kept):
    """assigned/kept: (T, E) 0/1 masks of routed vs surviving slots."""
    total = jnp.maximum(jnp.sum(assigned), 1.0)
    kept_per_token = jnp.sum(kept, axis=-1)
    routed_per_token = jnp.sum(assigned, axis=-1)
    fully_dropped = (routed_per_token > 0) & (kept_per_token == 0)
    return {
        "dropped_frac": lax.stop_gradient(1.0 - jnp.sum(kept) / total),
        "fully_dropped_frac": lax.stop_gradient(
            jnp.mean(fully_dropped.astype(jnp.float32))),
        "expert_load": lax.stop_gradient(jnp.sum(assigned, axis=0) / total),
    }


def switch_router(x, router_kernel, *, num_experts: int, capacity: int,
                  noise_rng=None, noise_scale: float = 0.0) -> RouterOutput:
    """Top-1 (Switch) routing with static capacity.

    Args:
      x: ``(T, D)`` tokens (local shard).
      router_kernel: ``(D, E)`` router weights (replicated).
      capacity: max tokens per expert **per shard**; overflow tokens are
        dropped (their combine weights are zero — the residual connection
        carries them, as in Switch) and counted in ``metrics``.
      noise_rng/noise_scale: optional jitter for load-balancing exploration.
    """
    probs = _router_probs(x, router_kernel, noise_rng, noise_scale)  # (T, E)
    expert = jnp.argmax(probs, axis=-1)                   # (T,)
    onehot = jax.nn.one_hot(expert, num_experts)          # (T, E)
    keep, dispatch = _assign_slots(onehot, capacity)      # (T,E), (T,E,C)
    gate = jnp.sum(probs * onehot, axis=-1, keepdims=True)      # (T, 1)
    combine = dispatch * gate[..., None]

    # Switch aux loss: E * sum_e fraction_tokens_e * mean_prob_e
    frac = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(frac * mean_prob)
    return RouterOutput(dispatch, combine, aux,
                        _router_metrics(onehot, keep))


def top2_router(x, router_kernel, *, num_experts: int, capacity: int,
                noise_rng=None, noise_scale: float = 0.0) -> RouterOutput:
    """Top-2 (GShard) routing with static capacity.

    Each token is routed to its two highest-probability experts with gates
    renormalized over the pair (``g_i = p_i / (p_1 + p_2)``).  Capacity
    accounting is GShard's: an expert's second-choice tokens queue BEHIND
    all of its first-choice tokens, so second choices are the first to drop
    under pressure.  The aux loss is the standard Switch/GShard
    load-balance term over FIRST choices (``E * sum_e frac1_e *
    mean_prob_e`` — differentiable through ``mean_prob``).
    """
    if num_experts < 2:
        # with E=1 the second argmax collapses onto the first: every token
        # is dispatched twice to the same expert, consuming two capacity
        # slots and silently halving effective capacity — reject loudly
        raise ValueError(
            f"top2_router requires num_experts >= 2, got {num_experts}; "
            "with a single expert the second choice duplicates the first "
            "(capacity silently halves) — use switch_router / router='top1'")
    probs = _router_probs(x, router_kernel, noise_rng, noise_scale)  # (T, E)
    e1 = jnp.argmax(probs, axis=-1)
    oh1 = jax.nn.one_hot(e1, num_experts)
    e2 = jnp.argmax(probs * (1.0 - oh1), axis=-1)
    oh2 = jax.nn.one_hot(e2, num_experts)
    g1 = jnp.sum(probs * oh1, axis=-1)
    g2 = jnp.sum(probs * oh2, axis=-1)
    denom = g1 + g2 + 1e-9
    g1n, g2n = g1 / denom, g2 / denom

    keep1, slot1 = _assign_slots(oh1, capacity)
    count1 = jnp.sum(oh1, axis=0, keepdims=True)                # (1, E)
    # second choices queue behind ALL first choices of that expert (when
    # first choices overflow, no slots remain for seconds — exact either way)
    keep2, slot2 = _assign_slots(oh2, capacity, base=count1)
    dispatch = slot1 + slot2                                    # (T, E, C)
    combine = (slot1 * g1n[:, None, None] + slot2 * g2n[:, None, None])

    frac1 = jnp.mean(oh1, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(frac1 * mean_prob)
    return RouterOutput(dispatch, combine, aux,
                        _router_metrics(oh1 + oh2, keep1 + keep2))


def get_router(name: str):
    """``'top1'`` -> :func:`switch_router`, ``'top2'`` ->
    :func:`top2_router`."""
    try:
        return {"top1": switch_router, "top2": top2_router}[name]
    except KeyError:
        raise ValueError(f"unknown router {name!r}; expected 'top1' or "
                         "'top2'") from None


def _local_ffn(expert_inputs, wi, wo):
    """(El, S, D) x (El, D, H) x (El, H, D) -> (El, S, D)."""
    h = jnp.einsum("esd,edh->esh", expert_inputs, wi)
    h = jax.nn.gelu(h)
    return jnp.einsum("esh,ehd->esd", h, wo)


def expert_parallel_ffn(x, router_kernel, wi_local, wo_local, *,
                        ep_axis: str = "ep", num_experts: int,
                        capacity: int, router: str = "top1",
                        noise_rng=None, noise_scale: float = 0.0):
    """MoE FFN with experts sharded over ``ep_axis``; call inside
    ``shard_map`` with tokens batch-sharded over the same axis.

    Args:
      x: ``(T_local, D)`` this shard's tokens.
      wi_local / wo_local: ``(E // ep, D, H)`` / ``(E // ep, H, D)`` — this
        shard's experts.
      router: ``'top1'`` (Switch) or ``'top2'`` (GShard; remember to size
        ``capacity`` for two assignments per token).

    Returns:
      ``(y, aux, metrics)``: ``(T_local, D)`` expert outputs (zero for
      dropped tokens — add the residual outside), the local aux loss, and
      the router's drop/load accounting (:class:`RouterOutput` metrics).
    """
    ep = lax.psum(1, ep_axis)
    local_e = wi_local.shape[0]
    dispatch, combine, aux, metrics = get_router(router)(
        x, router_kernel, num_experts=num_experts, capacity=capacity,
        noise_rng=noise_rng, noise_scale=noise_scale)

    # (T, E, C) x (T, D) -> (E, C, D): expert-major send buffer.  Global
    # expert e = s * (E//ep) + j lives on ep-shard s.
    sends = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    sends = sends.reshape((ep, local_e) + sends.shape[1:])     # (ep, El, C, D)
    # all_to_all(split 0, concat 0): chunk s goes to shard s; afterwards
    # axis 0 indexes the SOURCE shard (verified semantics — tests/test_moe.py)
    recvd = lax.all_to_all(sends, ep_axis, split_axis=0, concat_axis=0)
    inputs = recvd.transpose(1, 0, 2, 3).reshape(
        local_e, ep * capacity, x.shape[-1])                   # (El, ep*C, D)

    outputs = _local_ffn(inputs, wi_local, wo_local)           # (El, ep*C, D)

    # reverse route: chunk s of the capacity axis belongs to source shard s
    outputs = outputs.reshape(local_e, ep, capacity, x.shape[-1])
    outputs = outputs.transpose(1, 0, 2, 3)                    # (ep, El, C, D)
    back = lax.all_to_all(outputs, ep_axis, split_axis=0, concat_axis=0)
    expert_outputs = back.reshape(num_experts, capacity, x.shape[-1])

    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_outputs)
    return y, aux, metrics


def moe_ffn_reference(x, router_kernel, wi, wo, *, num_experts: int,
                      capacity: int, router: str = "top1"):
    """Unsharded reference: all experts local (for tests and 1-chip runs)."""
    dispatch, combine, aux, metrics = get_router(router)(
        x, router_kernel, num_experts=num_experts, capacity=capacity)
    inputs = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    outputs = _local_ffn(inputs, wi, wo)
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), outputs)
    return y, aux, metrics


# ---------------------------------------------------------------------------
# Dropless routing over the experts a chip holds
# ---------------------------------------------------------------------------


_LANES = 128                # tokens a step of the selection kernel ranks
_SELECT_TILE_BYTES = 2 ** 20    # of f32 scores in a grid step's block
_SELECT_MOST_EXPERTS = 1024   # rows of a (E, 128) slab the kernel unrolls


def _select_form(t: int, e: int, k: int, n_group: int = 1) -> str:
    """How :func:`_top_k` ranks ``(t, e)`` scores: ``'kernel'`` (the Pallas
    kernel ``bf_moe_select``) on a TPU where its tile serves the shape,
    ``'sorted'`` (``lax.top_k`` and ``take_along_axis``) elsewhere: the
    portable backend, the CPU tests, a token count that is not whole
    128-lane slabs (a model's 16-token init pass), experts or groups that
    are not whole sublanes of 8, more than ``_SELECT_MOST_EXPERTS``
    columns.  From the backend and the shape alone, as
    :func:`_sums_in_vmem` and ``row_sums._lookup_form``; tests ask for
    ``'kernel_interpret'``, the kernel in the Pallas interpreter, by
    patching this function."""
    tiled = (t % _LANES == 0 and e % (8 * n_group) == 0
             and k <= e <= _SELECT_MOST_EXPERTS)
    return ("kernel" if tiled and jax.default_backend() == "tpu"
            else "sorted")


def _keep_groups(scores, n_group: int, topk_group: int):
    """``scores (T, E)`` with the experts outside the ``topk_group`` best of
    ``n_group`` equal groups at minus infinity; a group's score is the sum
    of its two largest.  The sorted form's group stage."""
    t, e = scores.shape
    grouped = scores.reshape(t, n_group, e // n_group)
    _, kept = lax.top_k(lax.top_k(grouped, 2)[0].sum(-1), topk_group)
    open_groups = jnp.any(kept[..., None] == jnp.arange(n_group), axis=1)
    return jnp.where(open_groups[..., None], grouped, -jnp.inf).reshape(t, e)


def _best(x, ids, dead):
    """A slab's maximum over its rows and the lowest of ``ids`` that holds
    it, ``(1, lanes)`` each; ``ids`` reads ``dead`` where a row is out."""
    m = jnp.max(x, axis=0, keepdims=True)
    return m, jnp.min(jnp.where(x == m, ids, dead), axis=0, keepdims=True)


def _select_in_vmem(scores, values, k, n_group, topk_group, interpret):
    """The kernel form of :func:`_top_k`: ``scores (T, E)`` f32 (and
    ``values``, or ``None`` for the scores themselves) → ``idx (T, k)``
    int32, ``chosen (T, k)`` f32.

    **Tokens lie on the lanes**: the kernel reads ``(E, T)`` (XLA turns the
    matmul's output) in blocks of ``(E, tt)`` and ranks 128 tokens at a
    time, an ``(E, 128)`` slab in which a reduction over the experts is an
    element-wise pass over ``E / 8`` vregs and one over a vreg's sublanes.
    A round: the slab's maximum, the lowest live row that holds it (ties to
    the lower index), the value at that row summed through the row's own
    mask (one term that is not zero: the element to the bit), the row dead
    for the next round: minus infinity in the scores and ``E`` in the row
    ids, so a score that *is* minus infinity is still told from a dead row
    and listed in index order, as the sorted form lists it.  With groups
    a slab first gives each group the sum of its two best and counts, for
    each group, the groups that come before it; the rows of the groups not
    kept start dead.  Ids and values are written once, ``(k, tt)`` blocks
    that XLA turns back."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, e = scores.shape
    g = e // n_group
    tt = min(t, max(_LANES, _SELECT_TILE_BYTES // (4 * e) // _LANES * _LANES))
    own = values is None
    neg = -jnp.inf

    def kernel(*refs):
        s_ref, v_ref = (refs[0], None) if own else refs[:2]
        idx_ref, out_ref = refs[-2:]
        rows = lax.broadcasted_iota(jnp.int32, (e, _LANES), 0)

        def kept_groups(s):
            """``(e, 128)`` bool: the rows of the ``topk_group`` best
            groups, a group's score the sum of its two largest.  A group is
            kept where fewer than ``topk_group`` others come before it: a
            higher score, or the same at a lower index."""
            local = rows[:g]
            sums = []
            for j in range(n_group):
                sg = s[j * g:(j + 1) * g]
                m1, at = _best(sg, local, g)
                m2 = jnp.max(jnp.where(local == at, neg, sg), axis=0,
                             keepdims=True)
                sums.append(m1 + m2)
            kept = []
            for j in range(n_group):
                before = sum(jnp.where(
                    sums[i] >= sums[j] if i < j else sums[i] > sums[j], 1, 0)
                    for i in range(n_group) if i != j)
                kept.append(jnp.broadcast_to(before < topk_group,
                                             (g, _LANES)))
            return jnp.concatenate(kept, axis=0)

        def one_slab(c, carry):
            lanes = pl.ds(pl.multiple_of(c * _LANES, _LANES), _LANES)
            s, ids = s_ref[:, lanes], rows
            if n_group > 1:
                keep = kept_groups(s)
                s, ids = jnp.where(keep, s, neg), jnp.where(keep, rows, e)
            for i in range(k):
                m, at = _best(s, ids, e)
                mine = ids == at
                idx_ref[pl.ds(i, 1), lanes] = at
                out_ref[pl.ds(i, 1), lanes] = m if own else jnp.sum(
                    jnp.where(mine, v_ref[:, lanes], 0.0), axis=0,
                    keepdims=True)
                s, ids = jnp.where(mine, neg, s), jnp.where(mine, e, ids)
            return carry

        lax.fori_loop(0, tt // _LANES, one_slab, jnp.int32(0))

    block = pl.BlockSpec((e, tt), lambda i: (0, i))
    startup.kernel_traced("bf_moe_select")
    idx, chosen = pl.pallas_call(
        kernel, grid=(_ceil_div(t, tt),),
        in_specs=[block] * (1 if own else 2),
        out_specs=[pl.BlockSpec((k, tt), lambda i: (0, i))] * 2,
        out_shape=[jax.ShapeDtypeStruct((k, t), jnp.int32),
                   jax.ShapeDtypeStruct((k, t), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret, name="bf_moe_select",
    )(*((scores.T,) if own else (scores.T, values.T)))
    return idx.T, chosen.T


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _select(scores, values, k, n_group, topk_group, interpret):
    """:func:`_select_in_vmem` with the selection's gradient rule: the
    cotangent of ``chosen`` written densely at the chosen ids into
    ``values`` (into ``scores`` where they are their own values), one
    fusion and no scatter; the ids take none."""
    return _select_in_vmem(scores, values, k, n_group, topk_group, interpret)


def _select_fwd(scores, values, k, n_group, topk_group, interpret):
    idx, chosen = _select_in_vmem(scores, values, k, n_group, topk_group,
                                  interpret)
    return (idx, chosen), (idx, scores.shape[-1], values is None)


def _select_bwd(k, n_group, topk_group, interpret, res, g):
    idx, e, own = res
    # written as the kernel reads it, experts by tokens: the turn is free
    d = jnp.sum(jnp.where(
        idx.T[:, None] == jnp.arange(e, dtype=idx.dtype)[:, None],
        g[1].T[:, None], 0.0), axis=0).T
    return (d, None) if own else (None, d)


_select.defvjp(_select_fwd, _select_bwd)


def _top_k(scores, k: int, values=None, *, n_group: int = 1,
           topk_group: int = 1):
    """``(idx, chosen)``: the ids ``(…, k)`` int32 of the ``k`` largest
    ``scores (…, E)`` along the last axis in descending order, **ties to
    the lower index** (``lax.top_k``'s order), and ``values`` (default: the
    scores) at those ids.  The ids take no gradient and ``scores`` none
    through them; ``values`` take the cotangent of ``chosen`` at the chosen
    ids.  With ``n_group > 1`` the selection is group-limited first: only
    the experts of the ``topk_group`` best of ``n_group`` equal groups in
    index order (a group's score: the sum of its two largest) can be
    chosen.

    Two forms with one result to the bit (:func:`_select_form`, from the
    backend and the shape).  *The kernel* ``bf_moe_select``
    (:func:`_select_in_vmem`), one ``pallas_call`` a call, groups
    included: rounds of max over a slab of tokens in VMEM, so the scores
    are read from HBM once and nothing is sorted, gathered or scattered;
    its gradient is a dense write.  *Sorted*: ``lax.top_k`` over whole rows
    and ``take_along_axis``, whose gradient is a scatter-add; the portable
    form, the fallback for shapes no tile serves, and the reference the
    tests hold the kernel's bits against.  Scores that are NaN have no
    order in either form."""
    e = scores.shape[-1]
    lead = scores.shape[:-1]
    form = _select_form(math.prod(lead), e, k, n_group)
    if form == "sorted":
        if n_group > 1:
            scores = _keep_groups(scores.reshape(-1, e), n_group,
                                  topk_group).reshape(scores.shape)
        if values is None:
            chosen, idx = lax.top_k(scores, k)
            return idx, chosen
        _, idx = lax.top_k(scores, k)
        return idx, jnp.take_along_axis(values, idx, axis=-1)
    flat = None if values is None else values.reshape(-1, e)
    idx, chosen = _select(scores.reshape(-1, e), flat, k, n_group,
                          topk_group, form == "kernel_interpret")
    return idx.reshape(lead + (k,)), chosen.reshape(lead + (k,))


def _count_routing(weights, t, e, top_k, n_group=1, topk_group=1):
    """The routers' counters, with metrics on: a grouped call's kept groups
    and the rows of scores a ``bf_moe_select`` kernel ranked (none where
    the call took the sorted form)."""
    counters = []
    if n_group > 1:
        counters.append(("bf_moe_groups_kept_total", float(t * topk_group)))
    if _select_form(t, e, top_k, n_group) != "sorted":
        counters.append(("bf_moe_select_kernel_rows_total", float(t)))
    return metrics_comm.count(weights, counters)


def sigmoid_topk_router(x, router_kernel, bias, *, top_k: int,
                        scale: float = 1.0, n_group: int = 1,
                        topk_group: int = 1, eps: float = 0.0):
    """DeepSeek-V3's ``noaux_tc`` routing (arXiv:2412.19437 section 2.1.2):
    ``s = sigmoid(x @ W_g)`` in f32, the chosen set is the ``top_k`` largest
    of ``s + bias``, the weights are ``scale * s_i / sum of the chosen s`` —
    from ``s`` **without** the bias, which only steers the selection and
    takes no gradient.  ``eps`` is added to that sum where a model's
    normaliser has one (LFM2's ``s_i / (sum + 1e-6)``); at 0 nothing is
    added and the program is the one without it.

    With ``n_group > 1`` the selection is **group-limited** (the
    node-limited routing of the same section): the experts lie in
    ``n_group`` equal groups in index order, a group's score is the sum of
    its two largest ``s + bias``, the ``topk_group`` best groups are kept,
    and the ``top_k`` are taken among the kept groups' experts (the others
    at minus infinity).  Ties go to the lower index, groups and experts
    alike, and the chosen are listed by descending ``s + bias``
    (``lax.top_k``'s order).

    **How the set is picked** (:func:`_top_k`): on a TPU one Pallas kernel a
    call, ``bf_moe_select`` — ``top_k`` rounds of max over a slab of tokens
    in VMEM, the group stage in the same kernel, ``s`` read through each
    round's own mask — and elsewhere (the portable backend, a token count
    that is not whole 128-token slabs) ``lax.top_k`` over whole rows with
    ``take_along_axis``; the two agree to the bit in ids, weights and
    gradients, and nothing but the backend and the shape chooses.

    ``x (T, D)``, ``router_kernel (D, E)``, ``bias (E,)`` →
    ``idx (T, top_k)`` int32 expert ids, ``weights (T, top_k)`` f32.  The
    matmul runs at ``highest`` precision: on a TPU an f32 product is
    otherwise rounded to bf16, and the chosen set flips on that rounding.
    With metrics on, a grouped call adds its kept groups (``T *
    topk_group``) to ``bf_moe_groups_kept_total`` and a call the kernel
    served its ``T`` rows to ``bf_moe_select_kernel_rows_total``.
    """
    with jax.named_scope("bf.moe.route"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router_kernel.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        steer = s + lax.stop_gradient(bias.astype(jnp.float32))
        idx, chosen = _top_k(steer, top_k, s, n_group=n_group,
                             topk_group=topk_group)
        weights = scale * chosen
        total = jnp.sum(chosen, axis=-1, keepdims=True)
        weights = weights / (total + eps if eps else total)
    return idx, _count_routing(weights, *s.shape, top_k, n_group, topk_group)


def softmax_topk_router(x, router_kernel, *, top_k: int):
    """Softmax routing with the chosen weights renormalised: ``l = x @ W_r``
    in f32, the chosen set is the ``top_k`` largest ``l`` (ties to the lower
    index, listed by descending ``l``), the weights are ``exp(l_i) / sum of
    the chosen exp(l)``.  A softmax over all the experts followed by
    renormalising over the chosen gives the same weights, so the order of
    the two is no choice.  No bias, no scale.

    ``x (T, D)``, ``router_kernel (D, E)`` → ``idx (T, top_k)`` int32,
    ``weights (T, top_k)`` f32; the matmul at ``highest`` precision, as
    :func:`sigmoid_topk_router`'s and for its reason; the set is picked as
    there (:func:`_top_k`: the kernel ``bf_moe_select`` on a TPU, the
    round's maximum being the chosen logit; ``lax.top_k`` elsewhere), and a
    call the kernel served counts its ``T`` rows in
    ``bf_moe_select_kernel_rows_total``.
    """
    with jax.named_scope("bf.moe.route"):
        logits = jnp.dot(
            x.astype(jnp.float32), router_kernel.astype(jnp.float32),
            precision=lax.Precision.HIGHEST)
        idx, chosen = _top_k(logits, top_k)
        weights = jax.nn.softmax(chosen, axis=-1)
    return idx, _count_routing(weights, *logits.shape, top_k)


def _ceil_div(a, b):
    return -(-a // b)


def _row_buffer(n_rows: int, count: int, num_experts: int) -> int:
    """Height ``C`` of :func:`routed_experts`' row buffer, from the shapes
    alone: twice the rows uniform routing sends to ``count`` held experts of
    ``num_experts`` (``n_rows = T * k`` assignments), in whole 256-row tiles
    of the grouped matmul, and never more than every row.  A chip that
    holds every expert gets ``n_rows``: one pass by construction."""
    share = _ceil_div(2 * n_rows * count, num_experts)
    return min(n_rows, _ceil_div(share, 256) * 256)


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Tiles ``(rows, contraction, output)`` of one grouped product, which
    its two transposes in the backward pass reuse: 256
    rows (the largest power of two up to it dividing ``m``: the kernel needs
    exact row tiles) by, for each width, its largest divisor that is a
    multiple of 128 and at most 1024 (2048 -> 1024, 768 -> 768).  Measured
    on a v5e at 16 groups of about 256 rows of 2048 against experts 768
    wide (one layer's nine products, forward and backward, 4,096 held rows
    of 65,536): 6.35 ms, against 8.45 ms at (512, 256, 256) and 6.87 ms at
    512 rows by the same widths; and the time grows half as fast with the
    rows the router sends (PERF.md section 6, PR 28).

    **A width that is not whole lanes gets one tile of 128 columns a step**
    (nothing larger divides it) and the kernels take the ragged last one
    (``megablox`` masks the contraction's and drops the output's): right for
    a test's 32-wide model, slow at a published width.  An expert's width
    therefore never comes here ragged: :func:`_cast_experts` hands the
    products the experts' leaves padded with zero columns to whole lanes
    (1,856 -> 1,920 = 3 x 640: 3.4 % more products, every tile whole)."""
    tm = 256
    while m % tm:
        tm //= 2

    def widest(x):
        tile = 1024
        while tile > 128 and x % tile:
            tile -= 128
        return tile

    return tm, widest(k), widest(n)


def _grouped_products(sizes, backend):
    """``(product, transposes)`` over rows sorted by group; ``sizes (G,)``
    int32, the held groups' rows, which lie first: the rows past their sum
    are no group's.  ``product(rows (R, K), w (G, K, N)) -> (R, N)``: row
    ``r`` of group ``g`` times ``w[g]``; **what a row of no group gets is
    unspecified** (``lax.ragged_dot`` writes zeros, the Pallas kernel,
    ``megablox.gmm``, visits the groups' row tiles only and leaves the rest
    of its output as it found it: handing it the ``G`` sizes alone spares
    the pass over the whole output that zeroes the rows of a trailing
    group), so whoever reads a product selects the groups' rows.
    ``transposes(rows, w, g) -> (d_rows, d_w)`` for a cotangent ``g (R,
    N)``; ``d_w`` sums a group's rows alone."""
    if backend == "ragged":
        def product(rows, w):
            return lax.ragged_dot(rows, w, sizes)

        def transposes(rows, w, g):
            return jax.vjp(product, rows, w)[1](g)
    else:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

        interpret = backend == "gmm_interpret"

        def product(rows, w):
            return gmm(rows, w, sizes, rows.dtype,
                       _gmm_tiling(rows.shape[0], *w.shape[1:]),
                       interpret=interpret)

        def transposes(rows, w, g):
            # the forward's tiles serve its two transposes (measured so)
            tiling = _gmm_tiling(rows.shape[0], *w.shape[1:])
            return (gmm(g, w, sizes, rows.dtype, tiling, transpose_rhs=True,
                        interpret=interpret),
                    tgmm(rows.swapaxes(0, 1), g, sizes, w.dtype, tiling,
                         interpret=interpret))
    return product, transposes


ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
               "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def _hidden(activation, *products):
    """What ``W_down`` reads, from the rows' products with the leaves before
    it: ``activation(gate) * up`` of a gated expert's two, ``activation(up)``
    of an ungated expert's one."""
    if len(products) == 2:
        gate, up = products
        return ACTIVATIONS[activation](gate) * up
    up, = products
    return ACTIVATIONS[activation](up)


def _window(j, weights, order, ends, k, c):
    """Pass ``j`` of the sorted assignments: ``order[j*c : (j+1)*c]``.
    Returns the assignment ids, their tokens, the held groups' sizes in the
    window ``(count,)`` (their cumulative ``ends`` clipped to it; what is
    left of ``c`` is no group's, and the kernels skip it), which rows are a
    group's (``live (c,)``: the first ``sizes.sum()``) and each row's f32
    weight, 0 on the others."""
    start = j * c
    ids = lax.dynamic_slice(order, (start,), (c,))
    inside = jnp.clip(ends - start, 0, c)
    sizes = jnp.diff(inside, prepend=0)
    live = jnp.arange(c, dtype=jnp.int32) < inside[-1]
    w = jnp.where(live, weights.reshape(-1)[ids].astype(jnp.float32), 0.0)
    return ids, ids // k, sizes, live, w


def _sums_in_vmem(t: int, d: int, backend: str) -> bool:
    """Whether a pass's rows reach their tokens through
    :func:`row_sums.add_rows_at` (on a TPU, wherever
    :func:`row_sums.sums_tile` finds a tile) or through XLA's scatter-add
    (the portable ``'ragged'`` backend, and shapes the kernel cannot
    tile)."""
    return backend != "ragged" and row_sums.sums_tile(t, d) is not None


# ``acc[tokens[r]] += w[r] * (rows[0][r] + ..)`` for the first ``live`` rows of
# the buffer, under the expert layer's name in the trace
_add_rows_by_token = functools.partial(row_sums.add_rows_at,
                                       name="bf_moe_add_rows_by_token")


def _cast_experts(x, ws, backend):
    """The experts' leaves (``W_down`` last) in ``x``'s dtype and, **for the
    grouped-matmul kernels, their width in whole lanes**: a width ``F`` that
    is no multiple of 128 is padded with zero columns of the leaves before
    ``W_down`` and zero rows of ``W_down`` (an activation of 0 is 0, gated
    or not, and a zero row adds nothing), so that every tile of the
    products is whole (:func:`_gmm_tiling`).  The pad rides on the cast's
    own pass; ``lax.ragged_dot`` (the portable backend) takes any width as
    it is."""
    pad = 0 if backend == "ragged" else -ws[-1].shape[1] % _LANES
    with jax.named_scope("bf.moe.experts"):
        ws = tuple(w.astype(x.dtype) for w in ws)
        if pad:
            ws = (*(jnp.pad(w, ((0, 0), (0, 0), (0, pad))) for w in ws[:-1]),
                  jnp.pad(ws[-1], ((0, 0), (0, pad), (0, 0))))
        return ws


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _held_experts(x, weights, order, ends, experts, k, c, backend,
                  activation):
    """``y (T, D)``: every held assignment's ``weight * E(x[token])``,
    summed by token in f32, in passes of ``c`` rows over the held head of
    ``order`` (``ends``: the held groups' cumulative sizes); the sums by
    :func:`_add_rows_by_token` or by scatter-add (:func:`_sums_in_vmem`).
    ``experts``: the leaves ``(w_gate, w_up, w_down)`` of gated experts or
    ``(w_up, w_down)`` of ungated ones (:func:`_hidden`).
    One differentiation rule of its own: a loop with a traced trip count has
    no reverse mode, and the rule keeps nothing of a pass but the inputs."""
    *w_in, wd = _cast_experts(x, experts, backend)
    in_vmem = _sums_in_vmem(*x.shape, backend)

    def one_pass(j, y):
        with jax.named_scope("bf.moe.dispatch"):
            _, tokens, sizes, live, w = _window(j, weights, order, ends, k,
                                                c)
            rows = x[tokens]
        with jax.named_scope("bf.moe.experts"):
            product, _ = _grouped_products(sizes, backend)
            out = product(_hidden(activation,
                                  *(product(rows, wi) for wi in w_in)), wd)
        with jax.named_scope("bf.moe.combine"):
            if in_vmem:
                return _add_rows_by_token(
                    y, tokens, sizes.sum(), (out,), w,
                    interpret=backend == "gmm_interpret")
            return y.at[tokens].add(jnp.where(
                live[:, None], out.astype(jnp.float32) * w[:, None], 0.0))

    y = lax.fori_loop(0, _ceil_div(ends[-1], c), one_pass,
                      jnp.zeros(x.shape, jnp.float32))
    return y.astype(x.dtype)


def _held_experts_fwd(x, weights, order, ends, experts, k, c, backend,
                      activation):
    return (_held_experts(x, weights, order, ends, experts, k, c, backend,
                          activation),
            (x, weights, order, ends, experts))


def _held_experts_bwd(k, c, backend, activation, res, g):
    """The same passes: a pass's rows and products again, then their
    transposes; ``d_x``, ``d_weights`` and the experts' gradients add up
    over the passes in f32, ``d_x`` by token in the forward's form."""
    x, weights, order, ends, experts = res
    *w_in, wd = cast = _cast_experts(x, experts, backend)
    in_vmem = _sums_in_vmem(*x.shape, backend)

    def one_pass(j, carry):
        d_x, d_weights, d_ws = carry
        with jax.named_scope("bf.moe.dispatch"):
            ids, tokens, sizes, live, w = _window(j, weights, order, ends, k,
                                                  c)
            rows = x[tokens]
        with jax.named_scope("bf.moe.experts"):
            product, transposes = _grouped_products(sizes, backend)
            hidden, hidden_transpose = jax.vjp(
                functools.partial(_hidden, activation),
                *(product(rows, wi) for wi in w_in))
            out = product(hidden, wd)
        with jax.named_scope("bf.moe.combine"):
            g_rows = g[tokens].astype(jnp.float32)
            d_weights = d_weights.at[ids].add(jnp.where(
                live, (out.astype(jnp.float32) * g_rows).sum(axis=1), 0.0))
            d_out = (g_rows * w[:, None]).astype(x.dtype)
        with jax.named_scope("bf.moe.experts"):
            d_hidden, d_wd = transposes(hidden, wd, d_out)
            d_rows, d_w_in = zip(*(
                transposes(rows, wi, d)
                for wi, d in zip(w_in, hidden_transpose(d_hidden))))
            d_ws = tuple(acc + d.astype(jnp.float32)
                         for acc, d in zip(d_ws, (*d_w_in, d_wd)))
        with jax.named_scope("bf.moe.dispatch"):
            if in_vmem:
                d_x = _add_rows_by_token(
                    d_x, tokens, sizes.sum(), d_rows, None,
                    interpret=backend == "gmm_interpret")
            else:
                d_x = d_x.at[tokens].add(jnp.where(
                    live[:, None], functools.reduce(
                        jnp.add, (d.astype(jnp.float32) for d in d_rows)),
                    0.0))
        return d_x, d_weights, d_ws

    d_x, d_weights, d_ws = lax.fori_loop(
        0, _ceil_div(ends[-1], c), one_pass,
        (jnp.zeros(x.shape, jnp.float32),
         jnp.zeros(weights.size, jnp.float32),
         tuple(jnp.zeros(w.shape, jnp.float32) for w in cast)))
    # a width padded to whole lanes: the leaves' own columns and rows
    d_ws = tuple(d if d.shape == w.shape else d[:, :w.shape[1], :w.shape[2]]
                 for d, w in zip(d_ws, experts))
    return (d_x.astype(x.dtype),
            d_weights.reshape(weights.shape).astype(weights.dtype), None,
            None, tuple(d.astype(w.dtype) for d, w in zip(d_ws, experts)))


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def routed_experts(x, idx, weights, w_gate, w_up, w_down, *,
                   num_experts: int, held: Tuple[int, int],
                   backend: str = "auto", activation: str = "silu"):
    """This chip's share of a routed expert layer, without dropping a token.

    ``x (T, D)`` tokens; ``idx``/``weights (T, k)`` from the router, over
    all ``num_experts``; ``w_gate``/``w_up (count, D, F)`` and
    ``w_down (count, F, D)`` the experts that ``held = (first, count)``
    names: global experts ``first .. first + count - 1``.  **An expert
    takes one of two forms**, by its leaves: *gated*, ``E(x) = W_down
    (activation(W_gate x) * W_up x)``, three leaves, three grouped products
    forward and six back; or, with ``w_gate=None``, *ungated*, ``E(x) =
    W_down activation(W_up x)``, two leaves, two products forward and four
    back: no gate is built and nothing stands in for one.  ``activation``:
    ``'silu'``, ``'relu'`` or ``'relu2'`` (``relu(x) ** 2``).  ``F`` need
    not be whole lanes (:func:`_cast_experts` pads it for the kernels).  Returns
    ``(y, record)``: ``y[t] = sum over the chosen i that are held of
    weights[t, i] * E_i(x[t])`` in ``x.dtype`` — what the absent experts
    would add is left out, for the caller's exchange (or nothing, on one
    chip) to supply — and the routing record, not differentiated:
    ``rows_per_expert (count,)``, ``held_share`` (held assignments over
    all ``T * k``), ``row_passes`` and ``vmem_passes`` (int32, below).
    With metrics on, the record feeds the counters
    ``bf_moe_assignments_total``, ``bf_moe_assignments_held_total``,
    ``bf_moe_row_passes_total`` and ``bf_moe_vmem_passes_total``.

    The ``T * k`` assignments are sorted by expert (held experts first, the
    rest as one trailing group).  **The row buffer is ``C`` rows**
    (:func:`_row_buffer`: twice what uniform routing sends to the held
    experts, from the shapes alone; all ``T * k`` where every expert is
    held).  A pass gathers the next ``C`` sorted rows' tokens, runs the
    three grouped matmuls and the gate at that height, and adds
    ``weight * out`` into ``y`` by token in f32; ``row_passes = ceil(held
    rows / C)`` passes run, from the router's own counts: one while the
    router sends this chip at most twice its share, ``T * k / C`` if every
    assignment of every token lands here, none if no row is held (``y`` is
    then exactly zero).  Any routing fits, none is dropped, and the cost
    follows the rows the router sent.  The gradient runs the same passes
    (:func:`_held_experts`).

    **The sums by token** (``y``, and ``d_x`` in the gradient) **take one
    of two forms** (:func:`_sums_in_vmem`, from the backend and the shapes;
    ``vmem_passes`` is ``row_passes`` under the first and 0 under the
    second).  *In VMEM* (:func:`_add_rows_by_token`, the Pallas backends):
    the sums of a tile of tokens stay in VMEM while the blocks of the
    buffer that hold its rows are read, and each held row is added to its
    token's there; nothing is read past the last held row, and no f32 copy
    of the buffer is made.  *Scatter-add* of the pass's ``C`` rows at their
    tokens in HBM, which the TPU executes a row at a time: the portable
    backend's, and the fallback for a shape the kernel cannot tile.

    ``backend``: ``'gmm'`` the Pallas kernel (``megablox.gmm``), ``'ragged'``
    ``lax.ragged_dot`` (portable, what CI runs), ``'auto'`` the kernel on a
    TPU; ``'gmm_interpret'`` runs the kernel in the Pallas interpreter.
    """
    first, count = held
    if not (0 <= first and first + count <= num_experts and count >= 1):
        raise ValueError(f"held={held} is not a range of the "
                         f"{num_experts} experts")
    if w_down.shape[0] != count:
        raise ValueError(f"held {count} experts but the weights bring "
                         f"{w_down.shape[0]}")
    if backend == "auto":
        backend = "gmm" if jax.default_backend() == "tpu" else "ragged"
    if backend not in ("gmm", "gmm_interpret", "ragged"):
        raise ValueError(f"unknown backend {backend!r}")
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}; expected one "
                         f"of {sorted(ACTIVATIONS)}")
    t, k = idx.shape
    n_rows = t * k
    c = _row_buffer(n_rows, count, num_experts)
    with jax.named_scope("bf.moe.dispatch"):
        # held experts become groups 0 .. count-1, every other expert the
        # trailing group `count`
        group = jnp.minimum((idx.reshape(n_rows) - first) % num_experts,
                            count).astype(jnp.int32)
        # whole windows: the last one must not clamp
        order = jnp.pad(jnp.argsort(group).astype(jnp.int32),
                        (0, -n_rows % c))
        ends = (group[None, :] <= jnp.arange(count)[:, None]).sum(
            axis=1, dtype=jnp.int32)                 # held groups, cumulative
    experts = (w_up, w_down) if w_gate is None else (w_gate, w_up, w_down)
    y = _held_experts(x, weights, order, ends, experts, k, c, backend,
                      activation)
    row_passes = _ceil_div(ends[-1], c)
    vmem_passes = (row_passes if _sums_in_vmem(*x.shape, backend)
                   else jnp.zeros_like(row_passes))
    record = {"rows_per_expert": jnp.diff(ends, prepend=0),
              "held_share": ends[-1].astype(jnp.float32) / n_rows,
              "row_passes": row_passes, "vmem_passes": vmem_passes}
    y = metrics_comm.count(y, [("bf_moe_assignments_total", float(n_rows)),
                               ("bf_moe_assignments_held_total", ends[-1]),
                               ("bf_moe_row_passes_total", row_passes),
                               ("bf_moe_vmem_passes_total", vmem_passes)])
    return y, record
