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

The loss itself (the model's forward pass, flash attention included) is shared
with the system: this reference guards the optimizer and the gossip, the part
of the step that is this system's own.  The models' numerics against dense
f32 attention are the tier-1 tests' and ``chip_smoke.py``'s business.
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


def per_rank(tree, devices, copy=False):
    """Rank-stacked, mesh-sharded tree -> one tree per rank: each leaf the
    rank's own ``[1, ...]`` block on the rank's own device.  Without ``copy``
    the blocks share ``tree``'s buffers and die with them when a step donates
    it; with ``copy`` they are the reference's own."""
    def block(leaf, device):
        shard, = (s for s in leaf.addressable_shards if s.device == device)
        if copy:
            return jax.device_put(shard.data, device, may_alias=False)
        return shard.data
    return [jax.tree_util.tree_map(lambda leaf, d=d: block(leaf, d), tree)
            for d in devices]


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


def run(family, base_opt, atc, w, states, batches, devices):
    """``STEPS`` reference steps from ``states`` (per rank: params, model
    state, the base optimizer's state, as ``[1, ...]`` blocks on
    ``devices[rank]``) over ``batches[k][rank]``.  Returns the per-rank
    ``(params, model_state)`` and the ``[STEPS, ranks]`` losses.  ``states``
    is taken over: the list is emptied, the model and optimizer states are
    donated, the parameters are dropped leaf by leaf.

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
    return [s[:2] for s in states], np.asarray(jax.device_get(losses))


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
