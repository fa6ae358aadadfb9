"""Chip microbenchmark at ``smallthinker.t16384.solo``'s attention shapes
(T=16,384, 28 query heads over 4 key/value heads of 128, window 4,096): one
layer forward + backward, the key/value heads **repeated** up to the query
heads before the splash kernel (what ``local_attention`` does) against the
library's multi-query kernel vmapped over the key heads (no repeat), under the
causal mask and under the window; and the window layer at 512-row blocks.

The decision that ``local_attention`` keeps repeating at 7 query heads a key
head rests on it (PERF.md section 7, PR 34).  Prints the three fastest of six
timings a form, in ms, then one JSON line.

  chiprun -- python3 benchmarks/gqa_attention_bench.py
"""

import json
import time

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    BlockSizes, CausalMask, LocalMask, MultiHeadMask,
    make_splash_mha_single_device, make_splash_mqa_single_device)

T, H, HK, D, W = 16384, 28, 4, 128, 4096


def blocks(edge):
    compute = min(edge, 512)
    return BlockSizes(
        block_q=edge, block_kv=edge, block_kv_compute=compute,
        block_q_dkv=edge, block_kv_dkv=edge, block_kv_dkv_compute=compute,
        use_fused_bwd_kernel=True)


def mask(window):
    if window is None:
        return CausalMask((T, T))
    return LocalMask((T, T), window_size=(window - 1, 0), offset=0)


def repeated(window, edge):
    with jax.ensure_compile_time_eval():
        kernel = make_splash_mha_single_device(
            MultiHeadMask([mask(window)] * H), block_sizes=blocks(edge))

    def f(q, k, v):                      # q (T, H, D); k, v (T, HK, D)
        k, v = (jnp.repeat(x, H // HK, axis=1).transpose(1, 0, 2)
                for x in (k, v))
        return kernel(q.transpose(1, 0, 2), k, v).transpose(1, 0, 2)
    return f


def grouped(window, edge):
    share = H // HK
    with jax.ensure_compile_time_eval():
        kernel = make_splash_mqa_single_device(
            MultiHeadMask([mask(window)] * share), block_sizes=blocks(edge))

    def f(q, k, v):
        out = jax.vmap(kernel)(
            q.transpose(1, 0, 2).reshape(HK, share, T, D),
            k.transpose(1, 0, 2), v.transpose(1, 0, 2))
        return out.reshape(H, T, D).transpose(1, 0, 2)
    return f


def main():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (jax.random.normal(key, shape, jnp.bfloat16)
                  for key, shape in zip(keys, [(T, H, D), (T, HK, D),
                                               (T, HK, D), (T, H, D)]))
    out = {}
    for name, form, window, edge in [
            ("repeat.full.1024", repeated, None, 1024),
            ("grouped.full.1024", grouped, None, 1024),
            ("repeat.window.1024", repeated, W, 1024),
            ("grouped.window.1024", grouped, W, 1024),
            ("repeat.window.512", repeated, W, 512)]:
        f = form(window, edge)
        step = jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32)
                                    * w.astype(jnp.float32)),
            argnums=(0, 1, 2)))
        jax.block_until_ready(step(q, k, v))
        times = []
        for _ in range(6):
            start = time.perf_counter()
            jax.block_until_ready(step(q, k, v))
            times.append((time.perf_counter() - start) * 1e3)
        out[name] = sorted(times)[:3]
        print(name, out[name], flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
