"""One cell, from its data files to a compiled decentralized train step.

A cell is an entry of ``workloads`` in the manifest (``BENCHMARK.json``): a
configuration file (sizes, optimizer, family) under a traffic file (batch,
lengths, ranks, topology, communication).  Everything a cell needs is found by
name under the manifest's ``paths``: ``traffic/<name>.json``,
``families/<name>.py``, ``metrics/<name>.json``, ``reducers/<name>.py``; the
configuration by its ``file``.  Adding a cell is adding files and entries.

The step is the one the repo's trainers run (``bench.py`` and
``benchmarks/transformer_bench.py`` build it the same way): forward, backward,
``decentralized_optimizer`` and ``optax.apply_updates`` in one jitted
``shard_map`` over the gossip mesh, compiled once ahead of time.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu import topology as bf_topology
from bluefog_tpu.optim import CommunicationType, decentralized_optimizer
from bluefog_tpu.parallel.api import shard_map

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RING = 4  # seeded batches a rank cycles through

COMM = {"neighbor": CommunicationType.neighbor_allreduce,
        "allreduce": CommunicationType.allreduce,
        "none": CommunicationType.empty}
BASE_OPTIMIZERS = {
    "sgd": lambda o: optax.sgd(o["learning_rate"], momentum=o["momentum"]),
    "adamw": lambda o: optax.adamw(o["learning_rate"],
                                   weight_decay=o["weight_decay"]),
}


def load_json(path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Manifest:
    """``BENCHMARK.json`` and the directories its data files live in."""

    data: dict
    roots: tuple

    @classmethod
    def load(cls, path):
        data = load_json(path)
        return cls(data, tuple(os.path.join(REPO, p) for p in data["paths"]))

    def find(self, kind, name, ext=".json"):
        for root in self.roots:
            path = os.path.join(root, kind, name + ext)
            if os.path.exists(path):
                return path
        raise SystemExit(f"chipbench: no {kind}/{name}{ext} under "
                         f"{list(self.data['paths'])}")

    def module(self, kind, name):
        path = self.find(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def entry(self, section, name):
        for e in self.data[section]:
            if e["name"] == name:
                return e
        raise SystemExit(f"chipbench: {section} has no entry {name!r}; it "
                         f"has {[e['name'] for e in self.data[section]]}")

    def metrics_of(self, section, workload):
        """The metrics of ``section`` that this cell reports."""
        return [m for m in self.data[section]
                if workload in m.get("workloads", [workload])]


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    family: object
    ctx: object            # bluefog_tpu context: mesh, schedule, topology
    step: object           # compiled (state, batch) -> (state, loss[ranks])
    state: object          # (params, model_state, opt_state), rank-stacked
    ring: list             # RING rank-stacked batches, device resident
    param_shapes: object   # the rank-stacked parameter tree, as shapes

    @property
    def devices(self):
        return self.ctx.devices


@dataclasses.dataclass(frozen=True)
class Measured:
    """What a traced run hands each reducer.  ``trace`` is ``None`` where
    the capture holds no device lane (a CPU control-flow check); a reducer
    that finds nothing to read returns ``None``."""

    cell: Cell
    peaks: object              # (FLOP/s, bytes/s) of one chip, None on CPU
    step_ms: list              # steady untraced step times of the cell's step
    dispatch_ms: list          # host time inside each call into that step
    arm_step_ms: dict          # arm key -> median untraced step time
    throughput_per_chip: float
    compiles_in_window: int
    hlo: str                   # the compiled step, as text
    trace: object              # xplane.Trace or None
    traced_steps: int


def arm_key(overrides: dict) -> str:
    return json.dumps(overrides, sort_keys=True)


def open_cell(manifest: Manifest, workload: str):
    """The cell's configuration and traffic, from their files."""
    entry = manifest.entry("workloads", workload)
    config = load_json(os.path.join(
        REPO, manifest.entry("configs", entry["config"])["file"]))
    traffic = load_json(manifest.find("traffic", entry["traffic"]))
    if traffic["ranks"] != entry["chips"]:
        raise SystemExit(
            f"chipbench: cell {workload} asks for {entry['chips']} chips but "
            f"its traffic runs {traffic['ranks']} ranks")
    return config, traffic


def base_optimizer(config):
    o = config["optimizer"]
    return BASE_OPTIMIZERS[o["name"]](o)


def build_step(family, config, traffic, ctx):
    """The jitted, not yet compiled train step for this traffic (the cell's
    own, or an arm's with some traffic keys overridden)."""
    opt = decentralized_optimizer(
        base_optimizer(config), ctx.schedule, ctx.axis_name,
        communication_type=COMM[traffic["comm"]], atc=config["atc"],
        backend=traffic["backend"])
    ax = ctx.axis_name

    def train_step(state_blk, batch_blk):
        params, model_state, opt_state = jax.tree_util.tree_map(
            lambda t: t[0], state_blk)
        batch = jax.tree_util.tree_map(lambda t: t[0], batch_blk)
        (loss, model_state), grads = jax.value_and_grad(
            family.loss, has_aux=True)(params, model_state, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        state = jax.tree_util.tree_map(
            lambda t: t[None], (params, model_state, opt_state))
        return state, loss[None]

    step = jax.jit(shard_map(
        train_step, mesh=ctx.mesh, in_specs=(P(ax), P(ax)),
        out_specs=(P(ax), P(ax)), check_vma=False), donate_argnums=(0,))
    return opt, step


def build_init(family, opt, ctx):
    """One jitted call makes every rank's weights, optimizer state and batch
    ring on its own device from ``fold_in(seed, rank)``."""
    ax = ctx.axis_name

    def init(seed):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), lax.axis_index(ax))
        k_init, k_data = jax.random.split(key)
        params, model_state = family.init(k_init)
        state = (params, model_state, opt.init(params))
        ring = [family.make_batch(k) for k in jax.random.split(k_data, RING)]
        return jax.tree_util.tree_map(lambda t: jnp.asarray(t)[None],
                                      (state, ring))

    return jax.jit(shard_map(init, mesh=ctx.mesh, in_specs=(P(),),
                             out_specs=P(ax), check_vma=False))


def build_cell(manifest: Manifest, workload: str, seed: int) -> Cell:
    config, traffic = open_cell(manifest, workload)
    family = manifest.module("families", config["family"]).build(
        config, traffic)
    topo = getattr(bf_topology, traffic["topology"])(traffic["ranks"])
    ctx = bf.init(topology=topo, size=traffic["ranks"])
    opt, step = build_step(family, config, traffic, ctx)
    replicated = NamedSharding(ctx.mesh, P())
    state, ring = build_init(family, opt, ctx)(
        jax.device_put(np.uint32(seed), replicated))
    compiled = step.lower(state, ring[0]).compile()
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state[0])
    return Cell(workload, config, traffic, family, ctx, compiled, state,
                ring, shapes)


def build_arm(cell: Cell, overrides: dict):
    """The cell's step with some traffic keys overridden (a metric's arm),
    compiled for the same state so it can run in the cell's place."""
    traffic = {**cell.traffic, **overrides}
    _, step = build_step(cell.family, cell.config, traffic, cell.ctx)
    return step.lower(cell.state, cell.ring[0]).compile()
