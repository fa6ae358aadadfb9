"""Static verifier + lint passes for decentralized-communication programs.

Production decentralized training rests on invariants that, when violated,
surface as a hung barrier on a 128-chip job rather than a stack trace:
gossip weight matrices must be (doubly-)stochastic for decentralized SGD
to converge, ``collective_permute`` source/target pairs must form partial
permutations per step or programs deadlock, and Pallas collective-id
ranges must stay disjoint across concurrently-issued kernel families.
This package checks all of that *before* anything runs:

- :mod:`~bluefog_tpu.analysis.registry` — collective-id allocator /
  auditor: the window kernels' declarative id range ([2048, ...)),
  per-caller ``(base, limit)`` leases, and an audit pass that reports
  overlap between concurrent leases.
- :mod:`~bluefog_tpu.analysis.topology_check` — topology verifier:
  row/column stochasticity, self-loop sanity, strong connectivity,
  spectral gap, and period-union connectivity for time-varying schedules.
- :mod:`~bluefog_tpu.analysis.jaxpr_lint` — jaxpr comm-lint: traces a
  step function and walks the closed jaxpr for ``ppermute``/``psum``
  equations, verifying permutation bijectivity (deadlock-freedom), axis
  hygiene, host callbacks on the hot path, and buffer donation.
- :mod:`~bluefog_tpu.analysis.window_lint` — BF-WIN source lint: loops
  issuing pipelined (fire-and-forget) DCN window deposits must ``flush()``
  before their audit barrier, or the mass audit silently leaks.
- :mod:`~bluefog_tpu.analysis.lint` — the CLI
  (``python -m bluefog_tpu.analysis.lint``) running every pass over the
  repo's own topologies, optimizers, and examples; exits nonzero on
  violations.
"""

from bluefog_tpu.analysis.report import Diagnostic, LintError, LintReport
from bluefog_tpu.analysis.registry import (
    ID_FAMILIES,
    GLOBAL_LEASES,
    CollectiveIdLease,
    LeaseRegistry,
)
from bluefog_tpu.analysis.topology_check import (
    check_dynamic_schedules,
    check_mixing_matrix,
    check_schedule,
    check_topology,
    spectral_gap,
)
from bluefog_tpu.analysis.jaxpr_lint import (
    check_donation,
    check_permutation,
    lint_jaxpr,
    lint_step_fn,
)
from bluefog_tpu.analysis.window_lint import check_pipelined_flush
from bluefog_tpu.analysis.lockmodel import (
    LockModel,
    build_model,
    build_package_model,
)
from bluefog_tpu.analysis.concurrency_lint import (
    check_model,
    check_package,
    check_sources,
)
from bluefog_tpu.analysis.doc_lint import check_transport_doc

__all__ = [
    "LockModel",
    "build_model",
    "build_package_model",
    "check_model",
    "check_package",
    "check_sources",
    "check_transport_doc",
    "Diagnostic",
    "LintError",
    "LintReport",
    "ID_FAMILIES",
    "GLOBAL_LEASES",
    "CollectiveIdLease",
    "LeaseRegistry",
    "check_dynamic_schedules",
    "check_mixing_matrix",
    "check_schedule",
    "check_topology",
    "spectral_gap",
    "check_donation",
    "check_permutation",
    "lint_jaxpr",
    "lint_step_fn",
    "check_pipelined_flush",
]
