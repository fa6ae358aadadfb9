"""Collective-id allocator / auditor.

Pallas barrier semaphores are addressed by integer collective ids, and the
whole correctness story of concurrently-issued kernels rests on id
DISJOINTNESS: devices may be skewed in time across two data-independent
kernels (rank A already inside one window's delivery while rank B still
runs another's), and if both enumerate ids from overlapping ranges, one
kernel's barrier handshake absorbs the other's signals — the job wedges or,
worse, proceeds with a half-arrived payload.

The repo's one kernel family that takes such ids is the window deliver
kernel (``ops/pallas_gossip.py::deliver_pallas``); gossip runs on XLA's
collective-permutes and needs none (its fused kernel, which owned ids
[1024, 2048), went with PR 47):

==========  =====================  =======================================
family      id range               who enumerates inside it
==========  =====================  =======================================
windows     [2048, 2048 + 2^20 *   one CRC32-derived 1024-id bucket per
            1024)                  window name (``WINDOW_LEAF_CAP``)
==========  =====================  =======================================

1. **Declared leases** — each call site declares ``(base, limit)`` against
   a family; the registry validates the lease sits inside the family range
   and that the ids actually consumed (``used``) fit under ``limit``.
2. **Audit** — :meth:`LeaseRegistry.audit` reports every pairwise overlap
   between leases, conservatively treating all of them as concurrent (it
   sees leases, not data dependence).  Leases sharing an
   ``exclusive_group`` are exempt from mutual overlap checks — the
   sanctioned marker for call sites that can never be in flight
   together (the branches of one ``lax.switch``, or sequential calls
   chained by data dependence).

At trace time the window deliver path records its leases into the
process-global registry (:data:`GLOBAL_LEASES`).  The global registry
collects only inside a :meth:`LeaseRegistry.scope` block — wrap one
program's trace in a scope and the audit sees exactly the kernels that
program will issue; outside a scope, op-layer leases are dropped so
retraces and eager training loops neither accumulate unboundedly nor make
unrelated programs look concurrent.  The lint CLI and tests audit this way.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Iterator, List, Optional, Tuple

from bluefog_tpu.analysis.report import Diagnostic
from bluefog_tpu.utils import lockcheck as _lc

__all__ = [
    "ID_FAMILIES",
    "CollectiveIdLease",
    "LeaseRegistry",
    "GLOBAL_LEASES",
]

# Declarative family registry: family name -> [start, end) of the id space
# it owns.  The window family's end bound mirrors
# pallas_gossip.window_collective_id_base: 2^20 CRC32 buckets spaced
# WINDOW_LEAF_CAP (1024) ids apart, starting at 2048.
WINDOW_IDS: Tuple[int, int] = (2048, 2048 + (1 << 20) * 1024)

ID_FAMILIES: Dict[str, Tuple[int, int]] = {
    "windows": WINDOW_IDS,
}


@dataclasses.dataclass(frozen=True)
class CollectiveIdLease:
    """One call site's claim on a span of collective ids.

    ``[base, base + used)`` is what the call actually consumes;
    ``[base, limit)`` is what it declared.  Disjointness is audited on the
    *declared* span: two leases whose declared ranges overlap are a latent
    hazard even if today's ``used`` counts happen not to collide (a
    window's count grows with its tree's leaves).
    """

    owner: str
    base: int
    used: int
    limit: int
    family: str = "windows"
    exclusive_group: Optional[str] = None

    @property
    def span(self) -> Tuple[int, int]:
        return (self.base, self.limit)

    def validate(self) -> List[Diagnostic]:
        """Lease-local invariants (family fit + used-under-limit)."""
        diags: List[Diagnostic] = []
        fam = ID_FAMILIES.get(self.family)
        if fam is None:
            diags.append(Diagnostic(
                "error", "BF-ID001",
                f"unknown collective-id family {self.family!r}; known: "
                f"{sorted(ID_FAMILIES)}",
                pass_name="collective-ids", subject=self.owner))
            return diags
        lo, hi = fam
        if not lo <= self.base < hi:
            diags.append(Diagnostic(
                "error", "BF-ID002",
                f"base {self.base} outside the {self.family} id range "
                f"[{lo}, {hi})",
                pass_name="collective-ids", subject=self.owner))
        if not self.base < self.limit <= hi:
            diags.append(Diagnostic(
                "error", "BF-ID003",
                f"declared limit {self.limit} not inside ({self.base}, "
                f"{hi}] for family {self.family!r}",
                pass_name="collective-ids", subject=self.owner))
        if self.used < 0:
            diags.append(Diagnostic(
                "error", "BF-ID004",
                f"negative id consumption {self.used}",
                pass_name="collective-ids", subject=self.owner))
        elif self.base + self.used > self.limit:
            diags.append(Diagnostic(
                "error", "BF-ID005",
                f"consumes {self.used} ids from base {self.base}, "
                f"overrunning its declared limit {self.limit} by "
                f"{self.base + self.used - self.limit}",
                pass_name="collective-ids", subject=self.owner))
        return diags


class LeaseRegistry:
    """Accumulates :class:`CollectiveIdLease` records and audits them.

    Thread-safe: jit tracing can happen from multiple threads (the async
    window runtime's rank loops), and a lock around a list append is
    cheap at trace time.
    """

    def __init__(self, *, collect_only_in_scope: bool = False):
        self._lock = _lc.lock("analysis.registry.LeaseRegistry._lock")
        self._leases: List[CollectiveIdLease] = []
        self._collect_only_in_scope = collect_only_in_scope
        self._scope_depth = 0

    # -- recording -----------------------------------------------------------

    def lease(
        self,
        owner: str,
        *,
        base: int,
        used: int,
        limit: Optional[int] = None,
        family: str = "windows",
        exclusive_group: Optional[str] = None,
    ) -> CollectiveIdLease:
        """Record a lease.  ``limit=None`` declares the family's end bound
        (the pre-audit legacy behavior — allowed, but such leases overlap
        everything above their base, which is the point of the audit)."""
        if limit is None:
            limit = ID_FAMILIES.get(family, (0, base + max(used, 1)))[1]
        rec = CollectiveIdLease(owner=owner, base=base, used=used,
                                limit=limit, family=family,
                                exclusive_group=exclusive_group)
        with self._lock:
            # The global registry records only inside a scope(): op-layer
            # call sites lease on EVERY trace (retraces, eager loops), and
            # an unbounded accumulation across unrelated programs would
            # both leak memory in long-lived processes and make audit()
            # flag overlaps between programs that never run concurrently.
            if not self._collect_only_in_scope or self._scope_depth > 0:
                self._leases.append(rec)
        return rec

    def clear(self) -> None:
        with self._lock:
            self._leases.clear()

    @property
    def leases(self) -> List[CollectiveIdLease]:
        with self._lock:
            return list(self._leases)

    @contextlib.contextmanager
    def scope(self) -> Iterator["LeaseRegistry"]:
        """Audit one program at a time: snapshot-and-restore the lease
        list, so leases recorded inside the ``with`` body are exactly the
        ones :meth:`audit` sees (and they do not leak into later
        programs' audits).

        Scopes are process-global, not per-thread: a lease recorded by
        ANOTHER thread while this scope is open lands in (and is then
        discarded with) this scope's list.  Don't trace on other threads
        — e.g. the async window runtime's rank loops — while auditing;
        the lint CLI and tests are single-threaded, which is the
        supported auditing mode.  (Recording, by contrast, is fully
        thread-safe.)"""
        with self._lock:
            saved = list(self._leases)
            self._leases.clear()
            self._scope_depth += 1
        try:
            yield self
        finally:
            with self._lock:
                self._scope_depth -= 1
                self._leases[:] = saved

    # -- auditing ------------------------------------------------------------

    def audit(self) -> List[Diagnostic]:
        """Validate every lease and report overlaps between concurrent
        (non-same-``exclusive_group``) leases of the same family."""
        leases = self.leases
        diags: List[Diagnostic] = []
        for rec in leases:
            diags.extend(rec.validate())
        for i in range(len(leases)):
            for j in range(i + 1, len(leases)):
                a, b = leases[i], leases[j]
                if a.family != b.family:
                    continue
                if (a.exclusive_group is not None
                        and a.exclusive_group == b.exclusive_group):
                    continue
                lo = max(a.base, b.base)
                hi = min(a.limit, b.limit)
                if lo < hi:
                    diags.append(Diagnostic(
                        "error", "BF-ID010",
                        f"leases {a.owner!r} [{a.base}, {a.limit}) and "
                        f"{b.owner!r} [{b.base}, {b.limit}) overlap on "
                        f"[{lo}, {hi}): concurrent kernels would share "
                        "barrier semaphores (handshake absorption)",
                        pass_name="collective-ids",
                        subject=f"{a.owner}+{b.owner}"))
        return diags


#: Process-global registry the op layer records into at trace time.  It
#: collects ONLY inside a :meth:`LeaseRegistry.scope` block (the lint CLI
#: and tests wrap one program's trace in a scope): outside one, op-layer
#: leases are validated-and-dropped, so retraces and eager loops in a
#: long-lived process neither grow the list nor cross-contaminate audits.
GLOBAL_LEASES = LeaseRegistry(collect_only_in_scope=True)
