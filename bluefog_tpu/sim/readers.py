"""Reader-tree simulation: thousands of readers behind a relay tree.

The digital twin's read-path half: a deterministic discrete-event model
of one trainer publishing rounds at a fixed cadence into a
``degree``-ary relay tree ``depth`` tiers deep, with the leaf tier
fanning out to O(thousands) of readers.  It models exactly the
mechanisms :mod:`bluefog_tpu.relay` implements — per-hop skip-to-latest
(an edge carries at most one in-flight push; newer rounds overwrite the
pending one and count as skipped), strictly-forward landing (a node
drops rounds at or below its cursor), and re-parenting (a killed
relay's children re-attach to its parent after a reconnect delay,
cursor preserved) — on the virtual clock, so the tree's staleness and
delivery-cleanliness claims are checkable at a scale no live test
reaches.

Determinism: the BF-SIM001 contract — no wall clock, no ambient RNG;
per-edge latency jitter draws from :func:`~bluefog_tpu.sim.core.
rng_for` streams keyed by the edge's structural name.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from bluefog_tpu.sim.core import EventLoop, rng_for

__all__ = ["ReaderTreeConfig", "ReaderTreeReport", "run_reader_tree"]


@dataclasses.dataclass(frozen=True)
class ReaderTreeConfig:
    """Shape and physics of one reader-tree run.  ``hop_dt_s`` is the
    mean per-hop push latency (jittered ±50% per edge, seeded);
    ``kill`` schedules ``(t, tier, index)`` relay-node deaths; children
    re-parent to the dead relay's parent after ``reparent_dt_s``."""

    readers: int = 2048
    degree: int = 8
    depth: int = 2
    rounds: int = 150
    publish_dt_s: float = 0.01
    hop_dt_s: float = 0.002
    reparent_dt_s: float = 0.05
    seed: int = 0
    kill: Tuple[Tuple[float, int, int], ...] = ()

    def __post_init__(self):
        if self.readers < 1 or self.degree < 2 or self.depth < 0:
            raise ValueError("need readers >= 1, degree >= 2, depth >= 0")
        if self.rounds < 1 or self.publish_dt_s <= 0 or self.hop_dt_s < 0:
            raise ValueError("need rounds >= 1 and positive cadences")
        if self.readers > self.degree ** (self.depth + 1):
            # the honesty guard: a tree that cannot absorb the demand
            # at the declared degree must be rejected, not quietly
            # simulated with over-degree leaf fan-out — the live
            # fan-out limit would refuse those readers with ERR_BUSY
            raise ValueError(
                f"{self.readers} readers exceed tree capacity "
                f"{self.degree ** (self.depth + 1)} (= degree^(depth+1)"
                f" = {self.degree}^{self.depth + 1}); raise degree or "
                "depth")


@dataclasses.dataclass
class ReaderTreeReport:
    """What the acceptance predicates gate: per-tier worst staleness
    (in rounds, against the publisher's live round at delivery time),
    zero torn (a torn push is modeled as not-delivered — the wire
    contract — so any cursor regression or duplicate would surface in
    those counters instead), zero duplicates, zero regressions, and
    coverage (every reader kept receiving after the kills)."""

    readers: int = 0
    relays: int = 0
    deliveries: int = 0
    duplicates: int = 0
    regressions: int = 0
    torn: int = 0
    skipped_total: int = 0
    worst_staleness_by_tier: Dict[int, int] = dataclasses.field(
        default_factory=dict)
    min_reader_final_round: int = -1
    max_reader_final_round: int = -1
    readers_served: int = 0

    def as_dict(self) -> Dict:
        return {
            "readers": self.readers, "relays": self.relays,
            "deliveries": self.deliveries,
            "duplicates": self.duplicates,
            "regressions": self.regressions, "torn": self.torn,
            "skipped_total": self.skipped_total,
            "worst_staleness_by_tier": {
                str(k): v for k, v in
                sorted(self.worst_staleness_by_tier.items())},
            "min_reader_final_round": self.min_reader_final_round,
            "max_reader_final_round": self.max_reader_final_round,
            "readers_served": self.readers_served,
        }


class _Node:
    """One tree participant: a relay tier node or a leaf reader."""

    __slots__ = ("name", "tier", "parent", "children", "cursor", "alive",
                 "pending", "busy", "received", "dup", "reg", "skipped")

    def __init__(self, name: str, tier: int):
        self.name = name
        self.tier = tier
        self.parent: Optional["_Node"] = None
        self.children: List["_Node"] = []
        self.cursor = -1
        self.alive = True
        # per-child pending round (skip-to-latest: one in-flight push
        # per edge; a newer round overwrites the pending one)
        self.pending: Dict[str, int] = {}
        self.busy: Dict[str, bool] = {}
        self.received = 0
        self.dup = 0
        self.reg = 0
        self.skipped = 0


def run_reader_tree(cfg: ReaderTreeConfig) -> ReaderTreeReport:
    """Run one deterministic reader-tree scenario; see module doc."""
    loop = EventLoop()
    root = _Node("root", 0)
    relays: List[_Node] = []
    tiers: List[List[_Node]] = [[root]]
    # tier widths, computed leaf-up so EVERY tier's fan-out respects
    # the configured degree: the leaf tier is just wide enough for the
    # readers at <= degree each, and each tier above is just wide
    # enough for the tier below at <= degree each (the capacity guard
    # in the config guarantees the recursion bottoms out <= degree at
    # tier 1)
    widths: List[int] = []
    need = max(1, -(-cfg.readers // cfg.degree))
    for _t in range(cfg.depth, 0, -1):
        widths.append(need)
        need = max(1, -(-need // cfg.degree))
    widths.reverse()
    for t in range(1, cfg.depth + 1):
        tier_nodes = []
        for i in range(widths[t - 1]):
            node = _Node(f"t{t}r{i}", t)
            parent = tiers[t - 1][i % len(tiers[t - 1])]
            node.parent = parent
            parent.children.append(node)
            tier_nodes.append(node)
            relays.append(node)
        tiers.append(tier_nodes)
    leaf_tier = tiers[-1]
    readers: List[_Node] = []
    for i in range(cfg.readers):
        node = _Node(f"reader{i}", cfg.depth + 1)
        parent = leaf_tier[i % len(leaf_tier)]
        node.parent = parent
        parent.children.append(node)
        readers.append(node)

    pub_round = [-1]
    worst_stale: Dict[int, int] = {}

    lat_memo: Dict[Tuple[str, str], float] = {}

    def edge_latency(parent: _Node, child: _Node) -> float:
        # one seeded draw per EDGE, memoized: the jitter is structural
        # (keyed by the edge's names), so re-deriving the RNG on every
        # push would recompute the same constant in the hot path
        key = (parent.name, child.name)
        lat = lat_memo.get(key)
        if lat is None:
            rng = rng_for(cfg.seed, "edge", parent.name, child.name)
            lat = cfg.hop_dt_s * (0.5 + rng.random())
            lat_memo[key] = lat
        return lat

    def push(parent: _Node, child: _Node) -> None:
        """Schedule delivery of the parent's pending round to one
        child; at-most-one in flight per edge (skip-to-latest)."""
        if parent.busy.get(child.name) or child.name not in parent.pending:
            return
        parent.busy[child.name] = True
        loop.after(edge_latency(parent, child),
                   lambda: deliver(parent, child))

    def deliver(parent: _Node, child: _Node) -> None:
        parent.busy[child.name] = False
        rnd = parent.pending.pop(child.name, None)
        if rnd is None or not parent.alive:
            return  # a dead parent's in-flight push is a torn frame:
            # modeled as NOT delivered — the child's cursor is untouched
        if not child.alive or child.parent is not parent:
            return  # the child re-parented mid-flight; stale edge
        if rnd == child.cursor:
            child.dup += 1
        elif rnd < child.cursor:
            child.reg += 1
        else:
            if child.cursor >= 0:
                child.skipped += max(0, rnd - child.cursor - 1)
            child.cursor = rnd
            child.received += 1
            stale = max(0, pub_round[0] - rnd)
            if stale > worst_stale.get(child.tier, -1):
                worst_stale[child.tier] = stale
            land(child, rnd)
        if child.name in parent.pending:
            push(parent, child)

    def land(node: _Node, rnd: int) -> None:
        """Forward a landed round to every child edge."""
        for child in node.children:
            node.pending[child.name] = rnd
            push(node, child)

    def publish() -> None:
        if pub_round[0] + 1 >= cfg.rounds:
            return
        pub_round[0] += 1
        land(root, pub_round[0])
        root.cursor = pub_round[0]
        loop.after(cfg.publish_dt_s, publish)

    def kill(tier: int, index: int) -> None:
        victims = [n for n in relays if n.tier == tier]
        if not victims or index >= len(victims):
            return
        node = victims[index]
        node.alive = False
        node.pending.clear()
        grand = node.parent
        for child in list(node.children):
            # the re-parent: the child re-attaches to its grandparent
            # after the reconnect delay, CURSOR PRESERVED — the resumed
            # stream promises strictly above it, exactly the live
            # Subscriber.reparent contract
            def reattach(child=child, grand=grand):
                if not child.alive:
                    return
                child.parent = grand
                grand.children.append(child)
                if grand.cursor > child.cursor:
                    grand.pending[child.name] = grand.cursor
                    push(grand, child)
            loop.after(cfg.reparent_dt_s, reattach)
        node.children = []

    loop.at(0.0, publish)
    for (t, tier, index) in cfg.kill:
        loop.at(float(t), (lambda a, b: lambda: kill(a, b))(
            int(tier), int(index)))
    horizon = cfg.rounds * cfg.publish_dt_s \
        + (cfg.depth + 2) * (cfg.hop_dt_s * 2 + cfg.reparent_dt_s) + 1.0
    loop.run(until=horizon,
             max_events=40 * cfg.rounds * (cfg.readers + len(relays) + 8))

    rep = ReaderTreeReport(readers=len(readers), relays=len(relays))
    for node in readers + relays:
        rep.deliveries += node.received
        rep.duplicates += node.dup
        rep.regressions += node.reg
        rep.skipped_total += node.skipped
    rep.worst_staleness_by_tier = dict(worst_stale)
    finals = [r.cursor for r in readers]
    rep.min_reader_final_round = min(finals)
    rep.max_reader_final_round = max(finals)
    rep.readers_served = sum(1 for f in finals if f >= 0)
    return rep
