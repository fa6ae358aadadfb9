"""The plain reference a cell's steps are held to, and the comparison.

Independent of ``bluefog_tpu.optim`` and of the collectives in
``bluefog_tpu.ops``: each rank's shard goes through ``jax.value_and_grad`` of
the family's loss and the plain ``optax`` optimizer under a plain ``jax.jit``
on that rank's own device, and the mixing ``sum_s W[r, s] * x_s`` is done with
``jax.device_put`` copies between devices and f32 ``jnp`` arithmetic — no
collective, no ``shard_map``, no kernel.  ``W`` comes from the topology object
the benchmark built from the traffic file.  What is mixed follows the order
the configuration states: ``atc=False`` gives ``W @ p + update``, ``atc=True``
gives ``W @ (p + update)``.

Which comparison guards what:

- the parameters and the model state after ``STEPS`` steps, leaf by leaf
  (:func:`compare`): the optimizer as the system wraps it, the order of update
  and mixing, the mixing matrix, the wire's precision, the gossip kernels.  A
  step that leaves out a rank's update or a neighbour's share moves every leaf;
- the ``STEPS`` losses on every rank (``loss_rtol``): that the system's step
  saw the whole batch it was fed, from the state the window left;
- the model's own numerics only where the family brings
  ``reference_loss(params, model_state, batch)``, its forward pass and loss in
  plain ``jax.numpy`` and f32 (:func:`model_loss_error`,
  ``model_loss_rtol``).  Without it the loss (the model's forward pass, flash
  attention included) is shared with the system, and the models' numerics
  against dense f32 attention are the tier-1 tests' and ``chip_smoke.py``'s
  business.

The reference starts from the very state the window left.  Its copy waits on
the host while the system takes its steps (:func:`to_host`) and comes back to
the chip once the system's state has gone there in its turn
(:func:`from_host`), so that the chip holds one training state a rank at a
time.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

STEPS = 3


def mixing_matrix(topology, comm: str) -> np.ndarray:
    """``W`` the reference mixes with: the topology's, or the identity where
    the cell's step does not communicate."""
    if comm == "neighbor":
        return np.asarray(topology.weights, np.float64)
    if comm == "none":
        return np.eye(topology.size)
    raise SystemExit(f"chipbench: no plain reference for comm={comm!r}")


def per_rank(tree, devices):
    """Rank-stacked, mesh-sharded tree -> one tree per rank: each leaf the
    rank's own ``[1, ...]`` block on the rank's own device.  The blocks share
    ``tree``'s buffers and die with them when a step donates it."""
    def block(leaf, device):
        shard, = (s for s in leaf.addressable_shards if s.device == device)
        return shard.data
    return [jax.tree_util.tree_map(lambda leaf, d=d: block(leaf, d), tree)
            for d in devices]


def to_host(tree, devices):
    """:func:`per_rank`, copied to the host: one tree of ``numpy`` blocks per
    rank, read shard by shard (a ``device_get`` of the rank-stacked tree
    would gather every rank's block through one device).  The copies are
    exact and hold nothing of ``tree``."""
    def own(block, host):
        # the CPU backend hands out its own buffer, which would pin it
        # against donation and change under the system's steps
        if host.ctypes.data == block.unsafe_buffer_pointer():
            return host.copy()
        return host
    blocks = per_rank(tree, devices)
    return jax.tree_util.tree_map(own, blocks, jax.device_get(blocks))


def from_host(host_trees, devices):
    """What :func:`to_host` took, back on each rank's own device as arrays of
    their own: :func:`run` takes them over."""
    return [jax.device_put(tree, device)
            for tree, device in zip(host_trees, devices)]


@jax.jit
def _combine(weights, xs, update):
    """``sum_i weights[i] * xs[i] + update`` in f32, for one leaf."""
    acc = sum(w * x.astype(jnp.float32) for w, x in zip(weights, xs))
    return (acc + update.astype(jnp.float32)).astype(xs[0].dtype)


def _mix(w, leaves, updates, devices):
    """One leaf on every rank: ``sum_s w[r, s] * leaves[s] + updates[r]`` on
    ``devices[r]``.  A zero-weight rank is neither copied nor read."""
    out = []
    for r, device in enumerate(devices):
        used = [s for s in range(len(devices)) if w[r, s] != 0.0]
        out.append(_combine(
            tuple(np.float32(w[r, s]) for s in used),
            tuple(jax.device_put(leaves[s], device) for s in used),
            updates[r]))
    return out


def run(family, base_opt, atc, w, states, batches, devices,
        after_first_step=None):
    """``STEPS`` reference steps from ``states`` (per rank: params, model
    state, the base optimizer's state, as ``[1, ...]`` blocks on
    ``devices[rank]``) over ``batches[k][rank]``.  Returns the per-rank
    ``(params, model_state)`` and the ``[STEPS, ranks]`` losses.  ``states``
    is taken over: the list is emptied, the model and optimizer states are
    donated, the parameters are dropped leaf by leaf.  ``after_first_step``
    is called once every rank has mixed its first step: the reference's
    program is loaded and its state whole, the place to read memory.

    The mixing goes leaf by leaf over all ranks, waits for each leaf, and
    drops its old value and update once every rank has mixed it: on the chip
    the system's step keeps its scratch reserved after the window, and
    whole-tree copies of two neighbours' parameters did not fit beside it
    (PR 22).  The wait keeps the host from queueing, and so allocating, the
    whole tree's copies ahead of the device."""

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def local(params, model_state, opt_state, batch):
        params, model_state, opt_state, batch = jax.tree_util.tree_map(
            lambda t: t[0], (params, model_state, opt_state, batch))
        (loss, model_state), grads = jax.value_and_grad(
            family.loss, has_aux=True)(params, model_state, batch)
        updates, opt_state = base_opt.update(grads, opt_state, params)
        return jax.tree_util.tree_map(
            lambda t: t[None], (updates, model_state, opt_state, loss))

    states = [states.pop(0) for _ in range(len(states))]
    ranks = range(len(states))
    treedef = jax.tree_util.tree_structure(states[0][0])
    losses = []
    for k in range(STEPS):
        outs = [local(*states[r], batches[k][r]) for r in ranks]
        old = [treedef.flatten_up_to(states[r][0]) for r in ranks]
        upd = [treedef.flatten_up_to(outs[r][0]) for r in ranks]
        states = [(None, outs[r][1], outs[r][2]) for r in ranks]
        losses.append([outs[r][3][0] for r in ranks])
        del outs
        new = [[] for _ in ranks]
        for i in range(treedef.num_leaves):
            leaves = [old[r][i] for r in ranks]
            updates = [upd[r][i] for r in ranks]
            if atc:     # W @ (p + update)
                leaves = [p + u for p, u in zip(leaves, updates)]
                updates = [jnp.zeros_like(u) for u in updates]
            mixed = jax.block_until_ready(_mix(w, leaves, updates, devices))
            for r in ranks:
                new[r].append(mixed[r])
                old[r][i] = upd[r][i] = None
        states = [(treedef.unflatten(new[r]),) + states[r][1:]
                  for r in ranks]
        if k == 0 and after_first_step is not None:
            after_first_step()
    return [s[:2] for s in states], np.asarray(jax.device_get(losses))


def model_loss_error(family, params, model_state, batch):
    """The family's plain model against the system's, on one rank's
    ``[1, ...]`` blocks: ``family.reference_loss`` with every matmul at
    ``highest`` precision (on a TPU an f32 matmul is otherwise computed in
    bf16 passes) beside ``family.loss`` on the same inputs.  Returns the
    relative difference and the two losses."""
    def on_blocks(loss):
        return jax.jit(lambda *blocks: loss(*jax.tree_util.tree_map(
            lambda t: t[0], blocks)))
    with jax.default_matmul_precision("highest"):
        want = float(on_blocks(family.reference_loss)(
            params, model_state, batch))
    got = float(on_blocks(lambda *a: family.loss(*a)[0])(
        params, model_state, batch))
    return abs(got - want) / abs(want), want, got


@jax.jit
def _diff(got, want):
    def leaf(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.stack([jnp.max(jnp.abs(a - b)), jnp.max(jnp.abs(b))])
    return jax.tree_util.tree_map(leaf, got, want)


def largest_differences(got, want):
    """Per leaf, on the device: ``(max |got - want|, max |want|)``; only
    these scalars come to the host."""
    flat = jax.tree_util.tree_leaves_with_path(_diff(got, want))
    return [(jax.tree_util.keystr(path), *map(float, jax.device_get(v)))
            for path, v in flat]


def allowance(name, scale, tolerance):
    """What a leaf may differ by: ``rtol`` of the reference leaf's largest
    magnitude plus ``atol``, from the first of the tolerance's ``exceptions``
    whose ``leaves`` pattern is found in the leaf's path, else from the
    tolerance itself."""
    for rule in tolerance.get("exceptions", []):
        if re.search(rule["leaves"], name):
            return rule["rtol"] * scale + rule["atol"]
    return tolerance["rtol"] * scale + tolerance["atol"]


def compare(got_by_rank, want_by_rank, got_losses, want_losses, tolerance):
    """Does the system equal the reference?  A leaf agrees when its largest
    difference is within its :func:`allowance`; a loss when within
    ``loss_rtol``.  Returns ``(ok, leaves, loss_rel_err)`` with ``leaves``
    the ``(difference over allowed, path, difference, scale)`` of every leaf,
    worst first."""
    leaves = []
    for r, (got, want) in enumerate(zip(got_by_rank, want_by_rank)):
        for name, d, scale in largest_differences(got, want):
            allowed = allowance(name, scale, tolerance)
            over = d / allowed if d == d else float("inf")   # NaN fails
            leaves.append((over, f"rank{r}{name}", d, scale))
    leaves.sort(reverse=True)
    loss_err = float(np.max(np.abs(got_losses - want_losses)
                            / np.abs(want_losses)))
    ok = leaves[0][0] <= 1.0 and loss_err <= tolerance["loss_rtol"]
    return ok, leaves, loss_err
