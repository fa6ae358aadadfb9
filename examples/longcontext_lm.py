"""Long-context LM training with sequence parallelism (ring / Ulysses).

No reference counterpart (SURVEY.md §5: long-context absent upstream) — this
is the capability the framework adds on top of the gossip machinery: the
global sequence is sharded over the mesh axis, KV blocks rotate around the
ICI ring (:func:`bluefog_tpu.ops.ring_attention.ring_attention`), and each
device holds O(T/n) activations, n× longer context than a single chip.  With
``--attn ulysses`` the same model trains with all-to-all head/sequence
resharding instead; ``--remat`` additionally checkpoints each block.

Task: synthetic induction — the sequence is periodic with period P, so the
model can drive next-token loss to ~0 only by attending ≥ P tokens back;
with the period spanning multiple shards, learning proves the cross-shard
attention path works.

Run (8 virtual devices, global sequence 512 = 8 x 64):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/longcontext_lm.py --steps 60
"""

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # repo-root run

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu.models import GPTConfig, TransformerLM
from bluefog_tpu.ops.ring_attention import all_to_all_attention, ring_attention
from bluefog_tpu.parallel.api import shard_map


def make_batch(key, batch, t_global, vocab, period):
    """Periodic sequences: tokens repeat with the given period."""
    motif = jax.random.randint(key, (batch, period), 1, vocab)
    reps = -(-t_global // period)
    return jnp.tile(motif, (1, reps))[:, :t_global]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--attn", choices=["ring", "ring-zigzag", "ulysses"],
                    default="ring")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--t-local", type=int, default=64,
                    help="sequence tokens per device")
    ap.add_argument("--period", type=int, default=128,
                    help="repeat period; must divide the global length and "
                         "exceed t-local to force cross-shard attention")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--remat", action="store_true")
    args = ap.parse_args()
    bf.configure_compile_cache()

    n = len(jax.devices())
    bf.init()
    ctx = bf.get_context()
    t_global = n * args.t_local
    if args.period >= t_global:
        raise SystemExit("--period must be < global sequence length")
    if t_global % args.period:
        # otherwise the wrap-around target at the last position breaks the
        # periodicity and carries irreducible loss
        raise SystemExit(f"--period {args.period} must divide the global "
                         f"sequence length {t_global}")

    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=8, max_position=t_global, dtype=jnp.float32,
                    remat=args.remat)
    lm = TransformerLM(cfg)
    print(f"ranks={n} global_seq={t_global} attn={args.attn} "
          f"period={args.period} remat={args.remat}")

    zigzag = args.attn == "ring-zigzag"
    if args.attn == "ring":
        attn = functools.partial(ring_attention, axis_name=ctx.axis_name,
                                 causal=True)
    elif zigzag:
        attn = functools.partial(ring_attention, axis_name=ctx.axis_name,
                                 causal=True, layout="zigzag")
    else:
        attn = functools.partial(all_to_all_attention,
                                 axis_name=ctx.axis_name, causal=True,
                                 backend="auto")

    tokens = make_batch(jax.random.PRNGKey(1), args.batch, t_global, 256,
                        args.period)
    params = lm.init(jax.random.PRNGKey(0), tokens[:, :args.t_local])
    opt = optax.adam(args.lr)
    opt_state = opt.init(params)

    if zigzag:
        # the load-balanced layout's local block is NOT contiguous (front
        # chunk r + mirrored back chunk 2n-1-r), so global next-token
        # targets are computed in global order then resharded like the
        # tokens, and per-token global positions are built from the rank id
        from bluefog_tpu.ops.ring_attention import zigzag_shard

        if args.t_local % 2:
            raise SystemExit("--t-local must be even for ring-zigzag")
        targets_global = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], 1)
        tokens_in = zigzag_shard(tokens, n)
        targets_in = zigzag_shard(targets_global, n)
        c = args.t_local // 2
    else:
        tokens_in, targets_in = tokens, tokens  # targets via ppermute below

    def lm_step(params, opt_state, tokens_blk, tgt_blk):
        # tokens_blk: (B, T_local) — this shard's block of the sequence
        r = lax.axis_index(ctx.axis_name)

        def loss_fn(p):
            if zigzag:
                pos = jnp.concatenate(
                    [r * c + jnp.arange(c),
                     (2 * n - 1 - r) * c + jnp.arange(c)])[None, :]
                logits = lm.apply(p, tokens_blk, attn_fn=attn, positions=pos)
                tgt = tgt_blk
            else:
                logits = lm.apply(p, tokens_blk, attn_fn=attn,
                                  position_offset=r * tokens_blk.shape[1])
                # next-token targets across shard boundaries: first token of
                # the NEXT rank's block wraps in (global periodic sequence)
                nxt = lax.ppermute(
                    tokens_blk[:, :1], ctx.axis_name,
                    [(i, (i - 1) % n) for i in range(n)])
                tgt = jnp.concatenate([tokens_blk[:, 1:], nxt], axis=1)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tgt).mean()

        loss, g = jax.value_and_grad(loss_fn)(params)
        g = jax.tree_util.tree_map(lambda t: lax.pmean(t, ctx.axis_name), g)
        upd, opt_state = opt.update(g, opt_state)
        return (optax.apply_updates(params, upd), opt_state,
                lax.pmean(loss, ctx.axis_name))

    step = jax.jit(shard_map(
        lm_step, mesh=ctx.mesh,
        in_specs=(P(), P(), P(None, ctx.axis_name), P(None, ctx.axis_name)),
        out_specs=(P(), P(), P()), check_vma=False,
    ), donate_argnums=(0, 1))

    first = last = None
    t0 = time.perf_counter()
    for s in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens_in,
                                       targets_in)
        loss = float(loss)
        first = first if first is not None else loss
        last = loss
        if s % 10 == 0 or s == args.steps - 1:
            print(f"step {s:4d}  loss {loss:.4f}")
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0
    tps = args.steps * args.batch * t_global / dt
    print(f"\n{tps:,.0f} tokens/s total ({tps / n:,.0f}/chip)  "
          f"loss {first:.3f} -> {last:.3f}")
    if last > 0.7 * first:
        print("FAIL: loss barely moved — cross-shard attention suspect")
        sys.exit(1)
    print("OK — induction learned across shard boundaries")


if __name__ == "__main__":
    main()
