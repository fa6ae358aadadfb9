"""Record the scoped trace and the HLO text that ``test_scope_ms.py`` joins.
Run on the chip:

    chiprun --chips 1 -- python3 chipbench/tests/record_scoped_trace.py

Three steps of a small train step built the way ``cell.py`` builds a cell's
(forward, backward, ``decentralized_optimizer`` over a one-rank graph and
``optax.apply_updates`` in one jitted ``shard_map``), so that its compiled
text carries every kind of name ``reducers/scope_ms.py`` reads:

* ``jvp(TinyModel)``, ``transpose(jvp(TinyModel))`` and
  ``checkpoint/rematted_computation`` from JAX's transforms over a scoped
  model whose blocks are rematerialised;
* ``bf.optim.*`` from the program (its ``bf.gossip.*`` scopes do not reach
  the text: on one rank XLA folds the self weight to 1 and removes the
  gossip's multiply, concatenate and slices);
* a named, side-effect-free Pallas kernel inside the model, forward and
  backward (as the flash kernels are), the backward one under a name longer
  than 64 characters;
* an unnamed side-effecting Pallas kernel under ``shard_map`` (as the gossip
  kernels are): it is named ``shard_map.N`` and its line has no ``op_name``.

Writes ``chiprun_out/scoped.xplane.pb`` and ``chiprun_out/scoped.hlo.txt``;
copy both to ``chipbench/tests/data/``.
"""

import os
import shutil
import sys

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import xplane  # noqa: E402

from bluefog_tpu.optim import decentralized_optimizer  # noqa: E402
from bluefog_tpu.parallel.api import shard_map  # noqa: E402
from bluefog_tpu.topology import ExponentialTwoGraph  # noqa: E402
from bluefog_tpu.topology.schedule import build_schedule  # noqa: E402

STEPS, BLOCKS, WIDTH, BATCH = 3, 2, 512, 256
OUT = "chiprun_out"
LONG_NAME = ("scoped_bwd_kernel_block_q_major_512_block_q_512_block_k_major_512"
             "_block_k_512")


def scale_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 0.5


def halve(x, name=None, side_effect=False):
    return pl.pallas_call(
        scale_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name=name, compiler_params=pltpu.CompilerParams(
            has_side_effects=side_effect))(x)


@jax.custom_vjp
def named_kernel(x):
    return halve(x, "scoped_fwd_kernel")


named_kernel.defvjp(lambda x: (named_kernel(x), None),
                    lambda _, g: (halve(g, LONG_NAME),))


def block(w, b, x):
    return x + named_kernel(jnp.tanh(x @ w + b))


def loss_fn(params, batch):
    x = batch
    with jax.named_scope("TinyModel"):
        for i, (w, b) in enumerate(params):
            with jax.named_scope(f"block_{i}"):
                x = jax.checkpoint(block)(w, b, x)
    return jnp.mean(x * x)


def build(mesh):
    """The jitted step and a function that makes its state and batch."""
    opt = decentralized_optimizer(
        optax.adamw(1e-3), build_schedule(ExponentialTwoGraph(1)), "bf")

    def train_step(state_blk, batch_blk):
        params, opt_state = jax.tree_util.tree_map(lambda t: t[0], state_blk)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch_blk[0])
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        # stands in for a gossip kernel: unnamed, side-effecting
        params[0] = (halve(params[0][0], side_effect=True) * 2.0,
                     params[0][1])
        state = jax.tree_util.tree_map(lambda t: t[None], (params, opt_state))
        return state, loss[None]

    def init():
        keys = jax.random.split(jax.random.PRNGKey(0), BLOCKS + 1)
        params = [(jax.random.normal(k, (WIDTH, WIDTH)) * WIDTH ** -0.5,
                   jnp.zeros((WIDTH,))) for k in keys[:BLOCKS]]
        state = jax.tree_util.tree_map(lambda t: t[None],
                                       (params, opt.init(params)))
        return state, jax.random.normal(keys[-1], (1, BATCH, WIDTH))

    step = jax.jit(shard_map(
        train_step, mesh=mesh, in_specs=(P("bf"), P("bf")),
        out_specs=(P("bf"), P("bf")), check_vma=False), donate_argnums=(0,))
    return step, init


def main():
    step, init = build(Mesh(jax.devices()[:1], ("bf",)))
    state, batch = init()
    compiled = step.lower(state, batch).compile()
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "scoped.hlo.txt"), "w") as f:
        f.write(compiled.as_text())
    state, loss = compiled(state, batch)
    loss.block_until_ready()

    trace_dir = os.path.join(OUT, "scoped_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(STEPS):
        with jax.profiler.TraceAnnotation("chipbench.dispatch"):
            state, loss = compiled(state, batch)
        with jax.profiler.TraceAnnotation("chipbench.wait"):
            loss.block_until_ready()
    jax.profiler.stop_trace()
    path = xplane.newest(trace_dir)
    shutil.copy(path, os.path.join(OUT, "scoped.xplane.pb"))
    print(f"{jax.devices()[0].device_kind}: trace {os.path.getsize(path)} "
          f"bytes, text {len(compiled.as_text())} bytes, loss {loss}")


if __name__ == "__main__":
    main()
