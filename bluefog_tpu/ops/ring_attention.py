"""Long-context sequence/context parallelism: ring attention + all-to-all.

The reference is a pre-LLM data-parallel library with no sequence dimension
(SURVEY.md §5 "long-context": absent), but its core primitive — neighbor
exchange along a ring with compute overlapped — is exactly the communication
pattern of ring attention.  This module makes long context a first-class
capability of the framework by reusing the gossip machinery's ppermute ring:

- :func:`ring_attention` — blockwise attention with the KV blocks rotating
  around the mesh axis (one ``lax.ppermute`` per step, riding the ICI ring),
  combined with a numerically stable online softmax (flash-attention-style
  running max / denominator).  Memory per device is O(T/n), enabling
  sequences n× longer than single-device attention.
- :func:`all_to_all_attention` — DeepSpeed-Ulysses-style sequence parallelism:
  ``lax.all_to_all`` resharding sequence↔heads, full local attention, and the
  inverse reshard.  Fewer collective steps than the ring (2 all-to-alls vs
  n-1 permutes) but requires ``num_heads % axis_size == 0``.

Both run inside ``shard_map`` with the sequence dimension sharded over
``axis_name``; both are jit/grad compatible (the backward pass re-runs the
rotation in reverse via XLA's transpose of ``ppermute``).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "ring_attention",
    "all_to_all_attention",
    "local_attention",
    "zigzag_shard",
    "zigzag_unshard",
]

_NEG_INF = -1e30  # large finite negative: avoids -inf NaN traps in exp


def _flash_eligible(q, k, causal, q_offset, k_offset) -> bool:
    """Static eligibility check for the fused TPU flash kernel.

    The Pallas kernel (``jax.experimental.pallas.ops.tpu.flash_attention``)
    needs: a TPU backend, sequence length a multiple of its 128-row block,
    equal q/k lengths, and — because its causal mask is the standard aligned
    one — *static* offsets with ``q_offset == k_offset`` when causal.
    """
    if jax.default_backend() != "tpu":
        return False
    if not (isinstance(q_offset, int) and isinstance(k_offset, int)):
        return False
    if causal and q_offset != k_offset:
        return False
    t_q, t_k = q.shape[1], k.shape[1]
    return t_q == t_k and t_q >= 128 and t_q % 128 == 0 and q.shape[-1] >= 32


def _flash_block_sizes(t: int, block: Optional[int] = None):
    """Tile sizes for the fused TPU kernel.

    The library default is 128 everywhere (its own source marks parameter
    selection as a TODO), which leaves the MXU under-fed: on a v5e at
    T=4096 the default-tiled kernel measured *slower* than the dense path
    despite doing half the causal FLOPs.  Larger tiles amortize the grid
    loop; ``block`` overrides the target edge (the benchmark's --tune mode
    sweeps it), otherwise 512 — the largest tile that still fits the
    backward pass's working set in v5e VMEM comfortably.  Every edge is
    clamped to the largest power-of-two divisor of ``t`` (the kernel
    requires exact tiling; T is a multiple of 128 per `_flash_eligible`).
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    target = block or 512
    edge = 128
    while edge * 2 <= target and t % (edge * 2) == 0:
        edge *= 2
    return BlockSizes(
        block_q=edge, block_k_major=edge, block_k=edge, block_b=1,
        block_q_major_dkv=edge, block_k_major_dkv=edge, block_k_dkv=edge,
        block_q_dkv=edge, block_k_major_dq=edge, block_k_dq=edge,
        block_q_dq=edge)


def local_attention(q, k, v, *, causal: bool = False, scale: Optional[float] = None,
                    q_offset=0, k_offset=0, backend: str = "dense",
                    flash_block: Optional[int] = None):
    """Plain softmax attention on local blocks (also the Ulysses inner step).

    Shapes: ``q (B, Tq, H, D)``, ``k/v (B, Tk, H, D)`` → ``(B, Tq, H, D)``.
    ``q_offset``/``k_offset`` are the *global* positions of the first query /
    key row, used for causal masking of shifted blocks (may be traced).

    ``backend``: ``'dense'`` (default) materializes the (Tq, Tk) scores
    (portable, covered by CI); ``'flash'`` forces the fused Pallas TPU kernel
    (O(T) memory, fwd+bwd); ``'auto'`` picks flash whenever
    :func:`_flash_eligible` allows.  The *op-level* default is ``'dense'`` so
    that changing the runtime environment never silently switches which
    kernel a direct caller executes; the model layer
    (:mod:`bluefog_tpu.models.transformer`) opts into ``'auto'`` explicitly —
    that is the performance path, and its flash/dense parity is asserted by
    ``tests/test_flash_attention.py`` whenever a TPU is attached.
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])

    eligible = _flash_eligible(q, k, causal, q_offset, k_offset)
    if backend == "flash" and not eligible:
        raise ValueError(
            "backend='flash' requires a TPU backend, Tq == Tk with T a "
            "multiple of 128, head_dim >= 32, and static equal offsets when "
            f"causal; got backend={jax.default_backend()!r}, "
            f"Tq={q.shape[1]}, Tk={k.shape[1]}, D={q.shape[-1]}, "
            f"causal={causal}, offsets=({q_offset}, {k_offset}) — the Pallas "
            "kernel has no offset mask, so forcing it here would be "
            "silently wrong")
    use_flash = backend == "flash" or (backend == "auto" and eligible)
    if use_flash:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as _flash)

        # kernel layout is (B, H, T, D)
        out = _flash(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, sm_scale=scale,
            block_sizes=_flash_block_sizes(q.shape[1], flash_block))
        return out.transpose(0, 2, 1, 3).astype(q.dtype)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def _fold_block(state, q, k, v, *, scale, kpos0, qpos, masked: bool,
                kv_tile: int):
    """Flash-style inner step: fold one KV block into the running
    online-softmax state ``(m, denom, o)``.

    The block is processed in ``kv_tile``-sized key tiles by a ``lax.scan``
    whose body is rematerialized — the flash-attention recipe (tiled online
    softmax, O(t_q x tile) live score memory, activations recomputed in the
    backward pass) expressed in XLA-friendly form instead of a hand-written
    kernel.  ``masked=True`` applies the causal mask of global query
    positions ``qpos`` against key positions ``kpos0 + arange`` (only the
    diagonal block needs it; strictly-past blocks skip the mask entirely).
    """
    b, t_k, h, d = k.shape

    # largest divisor of t_k not exceeding kv_tile, so the promised
    # O(t_q x tile) live-score bound survives non-divisible block sizes; only
    # if nothing but degenerate divisors exist (prime-ish widths would scan
    # near-single-key tiles) does one whole-block tile beat a serial scan
    tile = min(kv_tile, t_k)
    while t_k % tile:
        tile -= 1
    if tile < min(8, t_k, kv_tile):
        tile = t_k
    nt = t_k // tile

    def fold_tile(carry, xs):
        m, denom, o = carry
        kt, vt, kt0 = xs  # (B, tile, H, D) x2, scalar global key offset
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, kt, preferred_element_type=jnp.float32
        ) * scale
        if masked:
            kpos = kt0 + jnp.arange(tile)
            scores = jnp.where((qpos[:, None] >= kpos[None, :])[None, None],
                               scores, _NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        denom = denom * alpha + p.sum(axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vt.dtype), vt,
            preferred_element_type=jnp.float32,
        )
        return (m_new, denom, o), None

    if nt == 1:
        return jax.checkpoint(fold_tile)(state, (k, v, kpos0))[0]
    k_tiles = k.reshape(b, nt, tile, h, d).transpose(1, 0, 2, 3, 4)
    v_tiles = v.reshape(b, nt, tile, h, d).transpose(1, 0, 2, 3, 4)
    offs = kpos0 + tile * jnp.arange(nt)
    state, _ = lax.scan(jax.checkpoint(fold_tile), state,
                        (k_tiles, v_tiles, offs))
    return state


def _zigzag_permutation(n: int, t_total: int):
    """Global row order for the load-balanced causal layout: the sequence is
    cut into ``2n`` chunks and rank ``r`` holds chunks ``r`` and ``2n-1-r``
    (a front chunk and its mirrored back chunk)."""
    import numpy as _np

    c, rem = divmod(t_total, 2 * n)
    if rem:
        raise ValueError(
            f"zigzag layout needs sequence length divisible by 2*axis_size; "
            f"got T={t_total}, n={n}")
    order = []
    for r_ in range(n):
        order.extend(range(r_ * c, (r_ + 1) * c))
        order.extend(range((2 * n - 1 - r_) * c, (2 * n - r_) * c))
    return _np.asarray(order)


def zigzag_shard(x, axis_size: int, axis: int = 1):
    """Reorder a *global* sequence axis into the zigzag layout, so that
    contiguous sharding over ``axis_size`` ranks gives each rank a front
    chunk and its mirrored back chunk (the load-balanced causal layout)."""
    idx = _zigzag_permutation(axis_size, x.shape[axis])
    return jnp.take(x, jnp.asarray(idx), axis=axis)


def zigzag_unshard(x, axis_size: int, axis: int = 1):
    """Inverse of :func:`zigzag_shard` (restores global sequence order)."""
    import numpy as _np

    idx = _zigzag_permutation(axis_size, x.shape[axis])
    return jnp.take(x, jnp.asarray(_np.argsort(idx)), axis=axis)


def ring_attention(
    q,
    k,
    v,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_tile: int = 512,
    layout: str = "contiguous",
):
    """Blockwise ring attention over a sequence-sharded mesh axis.

    Each rank holds the blocks ``q/k/v: (B, T_local, H, D)`` of a global
    sequence of length ``n * T_local`` laid out in rank order.  KV blocks
    rotate around the ring; each arrival is folded into the running
    (max, denominator, output) online-softmax state, so the result is exactly
    full attention over the global sequence, returned sequence-sharded.

    The rotation is the same single-shift circulant permutation the gossip
    schedule produces for :class:`~bluefog_tpu.topology.RingGraph` — on TPU it
    rides the ICI torus ring, and XLA overlaps the next block's ppermute with
    the current block's attention math.

    The inner step is flash-style (:func:`_fold_block`): ``kv_tile``-sized
    online-softmax tiles with rematerialization, so a rank's live score
    buffer is ``(B, H, t_q, kv_tile)`` regardless of block size.

    For ``causal=True`` the per-step work is dispatched on the arriving
    block's position: the diagonal block (processed first, so the running max
    is finite from step 0) runs with the triangle mask, strictly-past blocks
    run unmasked, and strictly-future blocks are **skipped outright** — only
    the taken branch executes, so the causal ring does ~half the attention
    FLOPs of the non-causal one instead of computing scores and masking them
    to zero.

    ``layout`` selects how the global sequence is assumed to be distributed:

    - ``'contiguous'`` (default): rank ``r`` holds rows ``[r*T_local,
      (r+1)*T_local)``.  Causal skipping then saves total FLOPs but is
      *imbalanced* — rank 0 skips almost every block, rank n-1 none — and
      since the ring is lock-stepped by its ppermutes, on a real slice the
      per-step critical path is the busiest rank and the saving shows up as
      idle time/energy, not wall-clock.
    - ``'zigzag'``: rank ``r`` holds chunks ``r`` and ``2n-1-r`` of the
      sequence cut into ``2n`` chunks (use :func:`zigzag_shard` /
      :func:`zigzag_unshard` to convert; output stays in zigzag order).
      At step 0 every rank folds its two (half-cost) masked diagonals plus
      the always-past ``q_back x k_front`` fold; every steady-state step
      folds **exactly two half-chunks** — that same ``q_back x k_front``
      fold plus one of ``q_front x k_front`` / ``q_back x k_back`` selected
      by the arriving block's origin — so the causal FLOP saving is
      identically load-balanced across ranks and
      becomes wall-clock on a lock-stepped slice.  (Non-causal math is
      position-independent, so ``layout`` only matters for ``causal=True``.)
    """
    n = lax.axis_size(axis_name)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    if causal and t_q != t_k:
        # block classification below (past/diagonal/future by rank index)
        # presumes equal shard widths, which ring *self*-attention always has
        raise ValueError(
            f"causal ring attention requires equal q/k shard widths, got "
            f"t_q={t_q}, t_k={t_k}")
    r = lax.axis_index(axis_name)

    state = (
        jnp.full((b, h, t_q), _NEG_INF, jnp.float32),
        jnp.zeros((b, h, t_q), jnp.float32),
        jnp.zeros((b, h, t_q, d), jnp.float32),
    )
    # the skip branch of the causal dispatch returns the carry unchanged, so
    # the carry must already be marked varying over the mesh axis or branch
    # output types (VMA) disagree with the fold branches
    state = jax.tree_util.tree_map(
        lambda t: lax.pcast(t, axis_name, to="varying"), state)

    shift = [(i, (i + 1) % n) for i in range(n)]

    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown layout {layout!r}")
    if causal and layout == "zigzag":
        return _ring_zigzag_causal(
            state, q, k, v, axis_name, n=n, r=r, scale=scale,
            kv_tile=kv_tile, shift=shift)

    qpos = r * t_q + jnp.arange(t_q)

    for s in range(n):
        src = (r - s) % n  # rank whose KV block we currently hold
        kpos0 = src * t_k
        if not causal:
            state = _fold_block(state, q, k, v, scale=scale, kpos0=kpos0,
                                qpos=qpos, masked=False, kv_tile=kv_tile)
        elif s == 0:
            # statically the diagonal block (src == r): triangle mask, and
            # the running max is finite from step 0
            state = _fold_block(state, q, k, v, scale=scale, kpos0=kpos0,
                                qpos=qpos, masked=True, kv_tile=kv_tile)
        else:
            # s > 0 never sees the diagonal again: the block is strictly
            # past (fold unmasked) or strictly future (skip outright — the
            # cond executes only the taken branch, so future blocks are free)
            state = lax.cond(
                src < r,
                lambda st, k, v, kp0: _fold_block(
                    st, q, k, v, scale=scale, kpos0=kp0, qpos=qpos,
                    masked=False, kv_tile=kv_tile),
                lambda st, k, v, kp0: st,
                state, k, v, kpos0,
            )
        if s != n - 1:
            k = lax.ppermute(k, axis_name, shift)
            v = lax.ppermute(v, axis_name, shift)

    _, denom, o = state
    out = o / jnp.maximum(denom[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _ring_zigzag_causal(state, q, k, v, axis_name, *, n, r, scale, kv_tile,
                        shift):
    """Load-balanced causal ring (zigzag layout; see :func:`ring_attention`).

    Rank ``r``'s local rows are [chunk ``r``; chunk ``2n-1-r``] of the global
    sequence in ``2n`` chunks of width ``c``.  For an arriving KV block from
    rank ``src`` the four (q-chunk, k-chunk) pairs classify statically or by
    ``src`` alone:

    - ``q_front(r) x k_back(2n-1-src)``: always strictly future — never
      folded.
    - ``q_back(2n-1-r) x k_front(src)``: always strictly past — folded
      unmasked every step.
    - ``q_front x k_front`` is past iff ``src < r``; ``q_back x k_back`` is
      past iff ``src > r``; exactly one of the two per step (both diagonal at
      ``s == 0``), so every rank folds exactly two ``c``-wide chunks per
      step — balanced, half the non-causal work.
    """
    t_q = q.shape[1]
    if t_q % 2:
        raise ValueError(
            f"zigzag layout needs an even local width, got t_q={t_q}")
    c = t_q // 2
    qf, qb = q[:, :c], q[:, c:]
    rel = jnp.arange(c)  # chunk-relative positions (diagonal masks align)

    # The front and back query halves never share a fold, so carry two
    # independent half-states (m, denom, o over c rows) and join once at the
    # end — no per-fold slice/concat traffic.
    def halve(t):
        return t[..., :c], t[..., c:]

    def halve_o(t):
        return t[..., :c, :], t[..., c:, :]

    m, denom, o = state
    front = (halve(m)[0], halve(denom)[0], halve_o(o)[0])
    back = (halve(m)[1], halve(denom)[1], halve_o(o)[1])

    def fold(st, qc, kc, vc, masked):
        return _fold_block(st, qc, kc, vc, scale=scale, kpos0=0, qpos=rel,
                           masked=masked, kv_tile=kv_tile)

    for s in range(n):
        kf, kb = k[:, :c], k[:, c:]
        vf, vb = v[:, :c], v[:, c:]
        if s == 0:  # statically src == r: two diagonals + back-vs-front past
            front = fold(front, qf, kf, vf, True)
            back = fold(back, qb, kb, vb, True)
            back = fold(back, qb, kf, vf, False)
        else:
            src = (r - s) % n
            back = fold(back, qb, kf, vf, False)
            front, back = lax.cond(
                src < r,
                lambda fr, bk, kf, vf, kb, vb: (fold(fr, qf, kf, vf, False), bk),
                lambda fr, bk, kf, vf, kb, vb: (fr, fold(bk, qb, kb, vb, False)),
                front, back, kf, vf, kb, vb,
            )
        if s != n - 1:
            k = lax.ppermute(k, axis_name, shift)
            v = lax.ppermute(v, axis_name, shift)

    denom = jnp.concatenate([front[1], back[1]], axis=-1)
    o = jnp.concatenate([front[2], back[2]], axis=-2)
    out = o / jnp.maximum(denom[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def all_to_all_attention(
    q,
    k,
    v,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    backend: str = "dense",
):
    """Ulysses-style sequence parallelism: reshard seq→heads, attend, reshard
    back.

    Input ``(B, T_local, H, D)`` sequence-sharded; requires ``H % n == 0``.
    Two ``lax.all_to_all`` collectives replace the ring's n-1 permutes —
    cheaper at moderate sequence lengths, while :func:`ring_attention` wins
    when T is huge or H < n.
    """
    n = lax.axis_size(axis_name)
    h = q.shape[2]
    if h % n != 0:
        raise ValueError(f"num_heads={h} not divisible by axis size {n}; "
                         "use ring_attention for head counts below the mesh size")

    def seq_to_heads(x):  # (B, T/n, H, D) -> (B, T, H/n, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):  # (B, T, H/n, D) -> (B, T/n, H, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qf, kf, vf = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = local_attention(qf, kf, vf, causal=causal, scale=scale,
                          backend=backend)
    return heads_to_seq(out)
