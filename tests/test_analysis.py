"""Static-analysis suite: each pass must CATCH its seeded violation and
stay quiet on every healthy built-in program.

The violations seeded here are the exact failure classes ISSUE/ADVICE
identified as silent at runtime: overlapping collective-id leases
(skewed-kernel handshake absorption), non-stochastic mixing rows
(per-round parameter rescaling), a disconnected period-union schedule
(rank pairs that never exchange information), and a non-bijective
ppermute (deadlock / double-delivery on a real mesh).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu import topology as T
from bluefog_tpu.analysis import (
    GLOBAL_LEASES,
    LeaseRegistry,
    LintError,
    LintReport,
    check_dynamic_schedules,
    check_mixing_matrix,
    check_permutation,
    check_schedule,
    check_topology,
    lint_step_fn,
    spectral_gap,
)
from bluefog_tpu.analysis.lint import run_all
from bluefog_tpu.ops import collectives as C
from bluefog_tpu.ops import pallas_gossip
from bluefog_tpu.optim import (
    DistributedGradientTrackingOptimizer,
    DistributedNeighborAllreduceOptimizer,
)
from bluefog_tpu.parallel.api import shard_map
from tests._util import REPO, clean_env

AXIS = "bf"


def _codes(diags):
    return {d.code for d in diags}


def _errors(diags):
    return [d for d in diags if d.severity == "error"]


# ---------------------------------------------------------------------------
# collective-id allocator / auditor
# ---------------------------------------------------------------------------


W0, W1, W2 = (2048 + k * 1024 for k in range(3))  # three window buckets


class TestLeaseRegistry:
    def test_overlapping_leases_caught(self):
        reg = LeaseRegistry()
        reg.lease("window:x", base=W0, used=10, limit=W1 + 64)
        reg.lease("window:y", base=W1, used=10, limit=W2)
        diags = reg.audit()
        assert "BF-ID010" in _codes(_errors(diags))

    def test_disjoint_leases_clean(self):
        reg = LeaseRegistry()
        reg.lease("window:x", base=W0, used=10, limit=W1)
        reg.lease("window:y", base=W1, used=10, limit=W2)
        assert not _errors(reg.audit())

    def test_exclusive_group_exempts_switch_branches(self):
        # the branches of one lax.switch are mutually exclusive at runtime
        # and legitimately share a base — same group, no overlap report
        reg = LeaseRegistry()
        reg.lease("dyn[0]", base=W0, used=4, limit=W1,
                  exclusive_group="switch0")
        reg.lease("dyn[1]", base=W0, used=4, limit=W1,
                  exclusive_group="switch0")
        assert not _errors(reg.audit())
        # ...but a DIFFERENT dynamic call sharing the base is still flagged
        reg.lease("dyn2[0]", base=W0, used=4, limit=W1,
                  exclusive_group="switch1")
        assert "BF-ID010" in _codes(_errors(reg.audit()))

    def test_used_overrunning_limit_caught(self):
        reg = LeaseRegistry()
        reg.lease("greedy", base=W0, used=1100, limit=W1)
        assert "BF-ID005" in _codes(_errors(reg.audit()))

    def test_base_outside_family_caught(self):
        reg = LeaseRegistry()
        reg.lease("stray", base=100, used=1, limit=W1)
        assert "BF-ID002" in _codes(_errors(reg.audit()))

    def test_windows_are_the_one_family(self):
        """The gossip kernels' ids [1024, 2048) went with the kernels
        (PR 47): a lease there is outside every family, and the family's
        name is unknown."""
        from bluefog_tpu.analysis import ID_FAMILIES

        assert sorted(ID_FAMILIES) == ["windows"]
        reg = LeaseRegistry()
        reg.lease("gossip", base=1024, used=1, limit=2048)
        assert "BF-ID002" in _codes(_errors(reg.audit()))
        reg = LeaseRegistry()
        reg.lease("gossip", base=1024, used=1, limit=2048, family="gossip")
        assert "BF-ID001" in _codes(_errors(reg.audit()))

    def test_scope_isolates_and_restores(self):
        reg = LeaseRegistry()
        reg.lease("outer", base=W0, used=1, limit=W1)
        with reg.scope():
            assert reg.leases == []
            reg.lease("inner", base=W0, used=1, limit=W1)
            assert [r.owner for r in reg.leases] == ["inner"]
        assert [r.owner for r in reg.leases] == ["outer"]


class TestWindowLeases:
    """The lease audit on the one family left: what a traced window
    delivery records, and what the lint pass audits."""

    def test_two_windows_in_one_program_lease_disjoint_buckets(
            self, devices8, monkeypatch):
        from bluefog_tpu.ops import windows as W

        monkeypatch.setenv("BLUEFOG_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setattr(pallas_gossip, "_claimed_bases",
                            dict(pallas_gossip._claimed_bases))
        sched = T.build_schedule(T.RingGraph(8))
        tree = {"w": jnp.zeros((8, 5)), "b": jnp.zeros((8, 1))}

        def body(xs):
            sx = W.win_create(xs, sched, AXIS, name="lease_probe_x")
            sy = W.win_create(xs, sched, AXIS, name="lease_probe_y")
            sx = W.win_put(sx, xs, AXIS, backend="pallas")
            sy = W.win_accumulate(sy, xs, AXIS, backend="pallas")
            return W.win_update(sx, AXIS)[0], W.win_update(sy, AXIS)[0]

        with GLOBAL_LEASES.scope() as reg:
            jax.make_jaxpr(_smap(_mesh(devices8), body))(tree)
            leases = {r.owner: r for r in reg.leases}
            assert not _errors(reg.audit())
        assert sorted(leases) == ["window:lease_probe_x",
                                  "window:lease_probe_y"]
        for name, rec in leases.items():
            assert rec.family == "windows" and rec.used == 2
            assert rec.base == pallas_gossip.window_collective_id_base(
                name.split(":")[1])
            assert rec.limit == rec.base + pallas_gossip.WINDOW_LEAF_CAP
        # the XLA transport takes no ids, and nothing is kept out of scope
        with GLOBAL_LEASES.scope() as reg:
            jax.make_jaxpr(_smap(_mesh(devices8), lambda xs: W.win_put(
                W.win_create(xs, sched, AXIS, name="lease_probe_x"), xs,
                AXIS, backend="xla").peer_bufs))(tree)
            assert reg.leases == []
        assert GLOBAL_LEASES.leases == []

    def test_two_names_in_one_bucket_are_caught_by_the_audit(self):
        # window_collective_id_base refuses the second claimant; a lease
        # table built without it (two owners, one bucket) fails the audit
        reg = LeaseRegistry()
        for owner in ("window:a", "window:b"):
            reg.lease(owner, base=W1, used=2,
                      limit=W1 + pallas_gossip.WINDOW_LEAF_CAP)
        assert "BF-ID010" in _codes(_errors(reg.audit()))

    def test_the_lint_pass_audits_the_window_probe_alone(self):
        from bluefog_tpu.analysis.lint import collective_id_pass

        report = LintReport()
        collective_id_pass(report, 8)
        assert report.ok, report.format()
        (diag,) = report.diagnostics
        assert diag.code == "BF-ID100" and "gossip" not in diag.message
        # the probe's bucket is released: linting claims nothing
        assert "lint_winput_probe" not in \
            pallas_gossip._claimed_bases.values()


# ---------------------------------------------------------------------------
# topology verifier
# ---------------------------------------------------------------------------


class TestTopologyChecks:
    def test_non_stochastic_matrix_caught(self):
        w = np.full((4, 4), 0.5)  # rows sum to 2
        diags = check_mixing_matrix(w, name="bad_rows")
        assert "BF-TOPO003" in _codes(_errors(diags))

    def test_negative_weight_caught(self):
        w = np.eye(4)
        w[0, 0], w[0, 1] = 1.5, -0.5
        assert "BF-TOPO002" in _codes(_errors(check_mixing_matrix(w)))

    def test_disconnected_graph_caught(self):
        # two isolated 2-cliques: stochastic but consensus splits
        block = np.full((2, 2), 0.5)
        w = np.block([[block, np.zeros((2, 2))],
                      [np.zeros((2, 2)), block]])
        diags = check_mixing_matrix(w, name="split")
        assert "BF-TOPO007" in _codes(_errors(diags))

    def test_zero_diagonal_caught(self):
        w = np.array([[0.0, 1.0], [1.0, 0.0]])  # periodic: oscillates
        assert "BF-TOPO005" in _codes(_errors(check_mixing_matrix(w)))

    def test_row_only_stochastic_warns_not_errors(self):
        star = T.StarGraph(8, center_rank=0)
        diags = check_topology(star)
        assert not _errors(diags)
        assert "BF-TOPO004" in {d.code for d in diags
                                if d.severity == "warning"}

    def test_require_doubly_stochastic_promotes_to_error(self):
        star = T.StarGraph(8, center_rank=0)
        diags = check_topology(star, require_doubly_stochastic=True)
        assert "BF-TOPO004" in _codes(_errors(diags))

    @pytest.mark.parametrize("size", [2, 4, 8])
    def test_all_builtin_topologies_clean(self, size):
        for topo in [
            T.ExponentialTwoGraph(size),
            T.ExponentialGraph(size, base=2),
            T.SymmetricExponentialGraph(size),
            T.RingGraph(size, 0),
            T.RingGraph(size, 1),
            T.RingGraph(size, 2),
            T.MeshGrid2DGraph(size),
            T.StarGraph(size),
            T.FullyConnectedGraph(size),
        ]:
            assert not _errors(check_topology(topo)), topo.name
            assert not _errors(check_schedule(T.build_schedule(topo))), \
                topo.name

    def test_spectral_gap_extremes(self):
        assert spectral_gap(T.FullyConnectedGraph(8)) == pytest.approx(1.0)
        block = np.full((2, 2), 0.5)
        split = np.block([[block, np.zeros((2, 2))],
                          [np.zeros((2, 2)), block]])
        assert spectral_gap(split) == pytest.approx(0.0, abs=1e-9)

    def test_non_permutation_schedule_slot_caught(self):
        good = T.build_schedule(T.RingGraph(8, 1))
        bad = T.GossipSchedule(
            size=8,
            perms=(((0, 1), (0, 2)),),  # rank 0 sends twice in one slot
            self_weights=good.self_weights,
            recv_weights=good.recv_weights,
            recv_src=good.recv_src,
            is_circulant=False,
            name="bad")
        assert "BF-TOPO010" in _codes(_errors(check_schedule(bad)))


class TestDynamicSchedules:
    def test_builtin_one_peer_periods_clean(self):
        for name, topos in [
            ("one_peer_exp2", T.one_peer_exponential_two_schedules(8)),
            ("one_peer_ring", T.one_peer_ring_schedules(8)),
        ]:
            diags = check_dynamic_schedules(topos, name=name)
            assert not _errors(diags), name
            assert "BF-TOPO101" in _codes(diags)

    def test_disconnected_period_union_caught(self):
        # every phase only pairs (0,1) and (2,3): ranks {0,1} and {2,3}
        # never exchange information no matter how long training runs
        pair = np.block([[np.full((2, 2), 0.5), np.zeros((2, 2))],
                         [np.zeros((2, 2)), np.full((2, 2), 0.5)]])
        diags = check_dynamic_schedules([pair, pair], name="never_crosses")
        assert "BF-TOPO022" in _codes(_errors(diags))

    def test_empty_schedule_caught(self):
        assert "BF-TOPO020" in _codes(_errors(check_dynamic_schedules([])))

    def test_per_phase_disconnection_allowed(self):
        # one-peer phases are individually disconnected BY DESIGN; only
        # the union matters — no BF-TOPO007 from any phase
        topos = T.one_peer_exponential_two_schedules(8)
        diags = check_dynamic_schedules(topos, name="one_peer")
        assert "BF-TOPO007" not in _codes(diags)


# ---------------------------------------------------------------------------
# jaxpr comm-lint
# ---------------------------------------------------------------------------


def _mesh(devices8):
    return Mesh(np.array(devices8), (AXIS,))


def _smap(mesh, body):
    return shard_map(body, mesh=mesh, in_specs=(P(AXIS),),
                     out_specs=P(AXIS), check_vma=False)


class TestJaxprLint:
    def test_check_permutation_duplicates(self):
        diags = check_permutation([(0, 1), (0, 2)], 4)
        assert "BF-COMM001" in _codes(_errors(diags))
        diags = check_permutation([(0, 2), (1, 2)], 4)
        assert "BF-COMM001" in _codes(_errors(diags))
        assert not _errors(check_permutation([(0, 1), (1, 0)], 4))

    def test_check_permutation_out_of_range(self):
        assert "BF-COMM003" in _codes(
            _errors(check_permutation([(0, 9)], 8)))

    def test_non_bijective_ppermute_in_traced_step_caught(self, devices8):
        # jax traces a duplicate-destination perm cleanly — the lint is
        # the only pre-run check (module docstring's motivating case)
        mesh = _mesh(devices8)

        def bad_step(x):
            return lax.ppermute(x, AXIS, [(0, 3), (1, 3), (2, 4)])

        diags = lint_step_fn(_smap(mesh, bad_step),
                             jnp.zeros((8, 4)), name="bad_step")
        assert "BF-COMM001" in _codes(_errors(diags))

    def test_gossip_step_clean(self, devices8):
        mesh = _mesh(devices8)
        sched = T.build_schedule(T.ExponentialTwoGraph(8))

        def step(x):
            return C.neighbor_allreduce(x, sched, AXIS)

        diags = lint_step_fn(_smap(mesh, step), jnp.zeros((8, 4)),
                             name="gossip")
        assert not _errors(diags)
        assert "BF-COMM100" in _codes(diags)

    def test_host_callback_warned(self, devices8):
        mesh = _mesh(devices8)

        def chatty(x):
            jax.debug.callback(lambda v: None, x)
            return x

        diags = lint_step_fn(_smap(mesh, chatty), jnp.zeros((8, 4)),
                             name="chatty")
        assert "BF-COMM010" in {d.code for d in diags
                                if d.severity == "warning"}

    def test_trace_failure_is_a_diagnostic_not_a_crash(self):
        def broken(x):
            raise RuntimeError("boom")

        diags = lint_step_fn(broken, jnp.zeros(4), name="broken")
        assert "BF-COMM020" in _codes(_errors(diags))

    @pytest.mark.parametrize("make_opt", [
        lambda: DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.05), topology=T.ExponentialTwoGraph(8),
            axis_name=AXIS),
        lambda: DistributedGradientTrackingOptimizer(
            optax.sgd(0.05), T.MeshGrid2DGraph(8), AXIS),
    ], ids=["dsgd", "gradient_tracking"])
    def test_distributed_optimizers_lint_clean(self, devices8, make_opt):
        mesh = _mesh(devices8)
        opt = make_opt()

        def body(c):
            w0 = jnp.zeros_like(c)
            st = opt.init(w0)

            def step(carry, _):
                w, s = carry
                upd, s = opt.update(w - c, s, w)
                return (optax.apply_updates(w, upd), s), None

            (w, _), _ = lax.scan(step, (w0, st), None, length=2)
            return w

        diags = lint_step_fn(_smap(mesh, body), jnp.zeros((8, 4)),
                             name="opt_step")
        assert not _errors(diags)


# ---------------------------------------------------------------------------
# report plumbing + CLI
# ---------------------------------------------------------------------------


class TestReport:
    def test_raise_if_errors(self):
        from bluefog_tpu.analysis import Diagnostic

        rep = LintReport([Diagnostic("error", "BF-ID010", "overlap")])
        assert not rep.ok
        with pytest.raises(LintError, match="BF-ID010"):
            rep.raise_if_errors()
        assert LintReport([Diagnostic("info", "BF-ID100", "fine")]).ok

    def test_invalid_severity_rejected(self):
        from bluefog_tpu.analysis import Diagnostic

        with pytest.raises(ValueError):
            Diagnostic("fatal", "BF-X", "nope")


class TestLintCli:
    def test_run_all_clean_on_own_programs(self):
        # the acceptance bar: every pass green over the repo's own
        # topologies, optimizers, and examples (trace pass included)
        report = run_all(size=8)
        assert report.ok, report.format()

    # pre-existing heavyweight (a fresh interpreter + the full
    # no-trace sweep): ~20s under full-suite load, and each new lint
    # pass (13 now, protocol pass included) legitimately extends it —
    # load-bearing tier-1 coverage, so a reviewed override instead of
    # slow-marking
    @pytest.mark.duration_budget(60)
    def test_cli_exits_zero(self):
        # the tier-1/CI hook: the module CLI itself (subprocess, fresh
        # interpreter) must exit 0 on the repo as committed.  --no-trace
        # keeps it to seconds; the traced passes run in-process above.
        proc = subprocess.run(
            [sys.executable, "-m", "bluefog_tpu.analysis.lint",
             "--no-trace", "--size", "8"],
            capture_output=True, text=True, timeout=300,
            cwd=REPO, env=clean_env())
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "lint: OK" in proc.stdout


# ---------------------------------------------------------------------------
# satellite guards (ADVICE lows)
# ---------------------------------------------------------------------------


class TestMoEGuards:
    def test_top2_router_rejects_single_expert(self):
        from bluefog_tpu.ops.moe import top2_router

        with pytest.raises(ValueError, match="num_experts >= 2"):
            top2_router(jnp.zeros((4, 8)), jnp.zeros((8, 1)),
                        num_experts=1, capacity=4)

    def test_moe_config_rejects_top2_single_expert(self):
        from bluefog_tpu.models.moe import GPTConfig, MoEConfig

        with pytest.raises(ValueError, match="num_experts >= 2"):
            MoEConfig(gpt=GPTConfig.tiny(), num_experts=1, router="top2")
        with pytest.raises(ValueError, match="unknown router"):
            MoEConfig(gpt=GPTConfig.tiny(), num_experts=4, router="top3")


# ---------------------------------------------------------------------------
# BF-WIN: pipelined window deposits must fence before their barrier
# ---------------------------------------------------------------------------


class TestWindowLint:
    def test_seeded_violation_unfenced_deposits(self):
        # the exact bug the rule exists for: fire-and-forget deposits, a
        # barrier that the mass audit trusts, and no flush in between
        from bluefog_tpu.analysis.window_lint import check_pipelined_flush

        src = (
            "def loop(peers, slots, payload, barrier, win, n_in):\n"
            "    for step in range(100):\n"
            "        for j in peers:\n"
            "            peers[j].deposit_async(slots[j], payload)\n"
            "    barrier.wait('stopped')\n"
            "    for k in range(n_in):\n"
            "        win.read(k, consume=True)\n"
        )
        diags = check_pipelined_flush(src, filename="seeded.py")
        assert any(d.code == "BF-WIN001" and d.severity == "error"
                   for d in diags), [d.format() for d in diags]

    def test_fenced_loop_is_clean(self):
        from bluefog_tpu.analysis.window_lint import check_pipelined_flush

        src = (
            "def loop(peers, slots, payload, barrier):\n"
            "    for step in range(100):\n"
            "        for j in peers:\n"
            "            peers[j].deposit_async(slots[j], payload)\n"
            "    for j in peers:\n"
            "        peers[j].flush()\n"
            "    barrier.wait('stopped')\n"
        )
        assert not check_pipelined_flush(src, filename="clean.py")

    def test_never_fenced_deposits_warn(self):
        from bluefog_tpu.analysis.window_lint import check_pipelined_flush

        src = (
            "def fire(peer, payload):\n"
            "    peer.deposit_async(0, payload)\n"
        )
        diags = check_pipelined_flush(src, filename="warn.py")
        assert [d.code for d in diags] == ["BF-WIN002"]
        assert diags[0].severity == "warning"

    def test_pipelined_ctor_receiver_deposit_counts(self):
        # .deposit() on a name bound from PipelinedRemoteWindow(...) is a
        # pipelined site too (the sync-spelling trap)
        from bluefog_tpu.analysis.window_lint import check_pipelined_flush

        src = (
            "def loop(addr, payload, barrier):\n"
            "    pw = PipelinedRemoteWindow(addr, 'w')\n"
            "    pw.deposit_async(0, payload)\n"
            "    barrier.wait('stopped')\n"
        )
        diags = check_pipelined_flush(src, filename="ctor.py")
        assert any(d.code == "BF-WIN001" for d in diags)

    def test_real_dsgd_loop_is_fenced(self):
        # the repo's own mp-dsgd body deposits pipelined and MUST stay
        # fenced — this is the regression tripwire for future edits
        import inspect

        from bluefog_tpu.analysis.window_lint import check_pipelined_flush
        from bluefog_tpu.runtime import async_windows

        diags = check_pipelined_flush(
            inspect.getsource(async_windows), filename="async_windows.py")
        assert not [d for d in diags if d.severity == "error"], \
            [d.format() for d in diags]

    def test_nested_deposit_closure_exempt_from_never_fenced(self):
        # a deposit closure whose CALLER fences (the bench's one_round
        # shape) must not trip BF-WIN002; BF-WIN001 still applies when
        # the closure itself races a barrier
        from bluefog_tpu.analysis.window_lint import check_pipelined_flush

        src = (
            "def run(stream, names, payloads):\n"
            "    def one_round():\n"
            "        for nm, p in zip(names, payloads):\n"
            "            stream.deposit_async(nm, 0, p)\n"
            "    for _ in range(10):\n"
            "        one_round()\n"
            "    stream.flush()\n"
        )
        assert not check_pipelined_flush(src, filename="closure.py")

    def test_window_pass_runs_in_sweep(self):
        # the bflint-tpu sweep includes the window pass (BF-WIN100 info)
        # and reports NO warnings of its own on the repo as committed
        # (false positives would break warnings-as-errors gating)
        report = run_all(size=8, trace=False)
        assert report.has("BF-WIN100"), report.format(verbose=True)
        assert report.ok, report.format()
        assert not [d for d in report.warnings
                    if d.code.startswith("BF-WIN")], report.format()

    def test_seeded_violation_mid_step_staged_apply(self):
        # BF-WIN004: folding the overlap buffer's staged round-(k-1)
        # mass from a hot-loop helper with no boundary vocabulary —
        # stale mixing applied mid-step
        from bluefog_tpu.analysis.window_lint import check_pipelined_flush

        src = (
            "def step(db, x, p):\n"
            "    staged, busy = db.apply_staged()\n"
            "    for k, buf, fresh in staged:\n"
            "        x += buf[:-1]\n"
            "        p += buf[-1]\n"
        )
        diags = check_pipelined_flush(src, filename="seeded.py")
        assert any(d.code == "BF-WIN004" and d.severity == "error"
                   for d in diags), [d.format() for d in diags]

    def test_boundary_named_staged_apply_is_clean(self):
        # the sanctioned shape: the apply lives in a function whose name
        # carries the round-boundary vocabulary (the runner's
        # fold_staged_at_round_boundary closure); module level is NOT ok
        from bluefog_tpu.analysis.window_lint import check_pipelined_flush

        src = (
            "def fold_staged_at_round_boundary(db, x, p):\n"
            "    staged, busy = db.apply_staged()\n"
            "    for k, buf, fresh in staged:\n"
            "        x += buf[:-1]\n"
            "        p += buf[-1]\n"
            "    return p\n"
        )
        assert not check_pipelined_flush(src, filename="clean.py")
        diags = check_pipelined_flush("db.apply_staged()\n",
                                      filename="mod.py")
        assert [d.code for d in diags] == ["BF-WIN004"]

    def test_overlap_apply_sites_are_boundary_only_in_repo(self):
        # repo-clean: both runners' overlap folds must keep their
        # boundary-vocabulary names — a rename or a new mid-loop call
        # site of apply_staged trips this before it ships
        import inspect

        from bluefog_tpu.analysis.window_lint import check_pipelined_flush
        from bluefog_tpu.runtime import async_windows

        src = inspect.getsource(async_windows)
        assert "apply_staged" in src  # the overlap path exists
        diags = check_pipelined_flush(src, filename="async_windows.py")
        assert not [d for d in diags if d.code == "BF-WIN004"], \
            [d.format() for d in diags]


# ---------------------------------------------------------------------------
# BF-RES: reconnect/retry loops must carry a budget or deadline
# ---------------------------------------------------------------------------


class TestResilienceLint:
    def test_seeded_violation_unbounded_reconnect(self):
        # the exact bug the rule exists for: while True around a connect
        # with no budget — the peer is never declared DEAD, the gossip
        # never heals, and a restarting peer's port is hammered forever
        from bluefog_tpu.analysis.resilience_lint import check_retry_budgets

        src = (
            "import socket\n"
            "def reconnect_forever(addr):\n"
            "    while True:\n"
            "        try:\n"
            "            return socket.create_connection(addr)\n"
            "        except OSError:\n"
            "            pass\n"
        )
        diags = check_retry_budgets(src, filename="seeded.py")
        assert any(d.code == "BF-RES001" and d.severity == "error"
                   for d in diags), [d.format() for d in diags]

    def test_itertools_count_is_unbounded_too(self):
        from bluefog_tpu.analysis.resilience_lint import check_retry_budgets

        src = (
            "import itertools, socket\n"
            "def reconnect(addr):\n"
            "    for _ in itertools.count():\n"
            "        try:\n"
            "            return socket.create_connection(addr)\n"
            "        except OSError:\n"
            "            pass\n"
        )
        diags = check_retry_budgets(src, filename="count.py")
        assert any(d.code == "BF-RES001" for d in diags)

    def test_backoff_iteration_is_clean(self):
        # the blessed shape: iterate a resilience.Backoff (budget by
        # construction) — exactly what DepositStream._recover does
        from bluefog_tpu.analysis.resilience_lint import check_retry_budgets

        src = (
            "import socket\n"
            "from bluefog_tpu.runtime.resilience import Backoff\n"
            "def reconnect(addr):\n"
            "    for delay in Backoff(budget=5):\n"
            "        try:\n"
            "            return socket.create_connection(addr)\n"
            "        except OSError:\n"
            "            continue\n"
        )
        assert not check_retry_budgets(src, filename="clean.py")

    def test_bounded_for_and_explicit_counter_are_clean(self):
        from bluefog_tpu.analysis.resilience_lint import check_retry_budgets

        src = (
            "import socket\n"
            "def a(addr):\n"
            "    for _ in range(5):\n"
            "        try:\n"
            "            return socket.create_connection(addr)\n"
            "        except OSError:\n"
            "            pass\n"
            "def b(addr, max_attempts):\n"
            "    attempts = 0\n"
            "    while True:\n"
            "        attempts += 1\n"
            "        if attempts > max_attempts:\n"
            "            raise OSError('unreachable')\n"
            "        try:\n"
            "            return socket.create_connection(addr)\n"
            "        except OSError:\n"
            "            pass\n"
        )
        assert not check_retry_budgets(src, filename="bounded.py")

    def test_plain_loops_without_connect_ignored(self):
        from bluefog_tpu.analysis.resilience_lint import check_retry_budgets

        src = (
            "def serve(sock):\n"
            "    while True:\n"
            "        data = sock.recv(4096)\n"
            "        if not data:\n"
            "            return\n"
        )
        assert not check_retry_budgets(src, filename="serve.py")

    def test_resilience_pass_runs_in_sweep_and_repo_is_clean(self):
        # the bflint-tpu sweep includes the pass (BF-RES100 info) and
        # the repo's own runtime — including DepositStream._recover and
        # run_supervised's restart loop — lints clean, for BOTH rules
        # (unbounded retries AND mid-round admissions)
        report = run_all(size=8, trace=False)
        assert report.has("BF-RES100"), report.format(verbose=True)
        assert not [d for d in report.diagnostics
                    if d.code in ("BF-RES001", "BF-RES002")], \
            report.format()


class TestAdmissionLint:
    """BF-RES002: an admission path without a round-boundary/quiesce
    marker is an error — re-admitting a peer mid-round changes the
    mixing weights under in-flight deposits (the torn state the exact
    mass audit exists to catch)."""

    def test_seeded_violation_midround_admission(self):
        from bluefog_tpu.analysis.resilience_lint import (
            check_admission_paths)

        src = (
            "def readmit_peer(board, peer):\n"
            "    if board.state(peer) == 3:\n"
            "        board.admit(peer)\n"
        )
        diags = check_admission_paths(src, filename="seeded.py")
        assert any(d.code == "BF-RES002" and d.severity == "error"
                   for d in diags), [d.format() for d in diags]

    def test_fenced_admission_is_clean(self):
        # the blessed shape: fence/flush (or a heal/replan/barrier) in
        # the same function marks the round boundary
        from bluefog_tpu.analysis.resilience_lint import (
            check_admission_paths)

        src = (
            "def gossip_round(board, peer, peers):\n"
            "    for h in peers:\n"
            "        h.flush()\n"
            "    board.admit(peer)\n"
        )
        assert not check_admission_paths(src, filename="clean.py")

    def test_heal_vocabulary_marks_the_boundary(self):
        from bluefog_tpu.analysis.resilience_lint import (
            check_admission_paths)

        src = (
            "def boundary(board, topo, dead, rejoined):\n"
            "    plan = heal(topo, dead - rejoined)\n"
            "    for j in rejoined:\n"
            "        board.admit(j)\n"
            "    return plan\n"
        )
        assert not check_admission_paths(src, filename="healclean.py")

    def test_state_machine_primitive_is_exempt(self):
        # the definition of admit() itself cannot mention its caller's
        # barrier — the rule is for callers
        from bluefog_tpu.analysis.resilience_lint import (
            check_admission_paths)

        src = (
            "class Core:\n"
            "    def admit(self):\n"
            "        self._set(0, admitted=True)\n"
        )
        assert not check_admission_paths(src, filename="prim.py")

    def test_functions_without_admission_ignored(self):
        from bluefog_tpu.analysis.resilience_lint import (
            check_admission_paths)

        src = (
            "def plain(x):\n"
            "    return x + 1\n"
        )
        assert not check_admission_paths(src, filename="plain.py")


# ---------------------------------------------------------------------------
# BF-CTL: controller actuation only at round boundaries
# ---------------------------------------------------------------------------


class TestControlLint:
    """BF-CTL001: a CommPlan actuation (apply_plan / set_comm_every /
    set_codec / *actuate*) outside a round-boundary/quiesce context is
    an error — the BF-RES002 invariant on the control plane."""

    def test_seeded_violation_midround_actuation(self):
        from bluefog_tpu.analysis.control_lint import check_actuation_paths

        src = (
            "def retune(ctl, topo, members):\n"
            "    topo2 = ctl.apply_plan(topology=topo, members=members)\n"
            "    return topo2\n"
        )
        diags = check_actuation_paths(src, filename="seeded.py")
        assert any(d.code == "BF-CTL001" and d.severity == "error"
                   for d in diags), [d.format() for d in diags]

    def test_seeded_violation_midround_codec_and_cadence(self):
        from bluefog_tpu.analysis.control_lint import check_actuation_paths

        for call in ("stream.set_codec('f32')",
                     "set_comm_every(state, 4)"):
            src = f"def tune(stream, state):\n    {call}\n"
            diags = check_actuation_paths(src, filename="seeded2.py")
            assert any(d.code == "BF-CTL001" for d in diags), call

    def test_boundary_vocabulary_is_clean(self):
        from bluefog_tpu.analysis.control_lint import check_actuation_paths

        src = (
            "def actuate_at_round_boundary(ctl, topo, members, peers):\n"
            "    for h in peers:\n"
            "        h.flush()\n"
            "    return ctl.apply_plan(topology=topo, members=members)\n"
        )
        assert not check_actuation_paths(src, filename="clean.py")

    def test_boundary_vocabulary_matches_whole_words_only(self):
        # `background` must not pass as "round", `self.health` as
        # "heal", `flushed_bytes` as "flush" — the serving-lint
        # whole-word discipline applies here too
        from bluefog_tpu.analysis.control_lint import check_actuation_paths

        src = (
            "def tune(ctl, topo, members, background, flushed_bytes):\n"
            "    if ctl.health and background:\n"
            "        return ctl.apply_plan(topology=topo,\n"
            "                              members=members)\n"
        )
        diags = check_actuation_paths(src, filename="sneaky.py")
        assert any(d.code == "BF-CTL001" for d in diags), \
            [d.format() for d in diags]
        # while real snake-case markers still pass
        src_ok = (
            "def tune_at_round_boundary(ctl, topo, members):\n"
            "    return ctl.apply_plan(topology=topo, members=members)\n"
        )
        assert not check_actuation_paths(src_ok, filename="ok.py")

    def test_actuation_primitive_itself_is_exempt(self):
        from bluefog_tpu.analysis.control_lint import check_actuation_paths

        src = (
            "class CommController:\n"
            "    def apply_plan(self, *, topology, members):\n"
            "        return plan_topology(topology, members, self.plan)\n"
        )
        assert not check_actuation_paths(src, filename="prim.py")

    def test_functions_without_actuation_ignored(self):
        from bluefog_tpu.analysis.control_lint import check_actuation_paths

        assert not check_actuation_paths(
            "def plain(x):\n    return x + 1\n", filename="plain.py")

    def test_repo_control_surfaces_clean(self):
        """The sweep's own targets — the control package and the
        runtime loops it is wired into — carry no BF-CTL001."""
        import glob

        from bluefog_tpu.analysis.control_lint import check_file

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        targets = sorted(glob.glob(os.path.join(
            root, "bluefog_tpu", "control", "*.py")))
        targets += sorted(glob.glob(os.path.join(
            root, "bluefog_tpu", "runtime", "*.py")))
        assert targets
        errs = [d for p in targets for d in check_file(p)
                if d.severity == "error"]
        assert not errs, [d.format() for d in errs]


# ---------------------------------------------------------------------------
# BF-SRV: snapshot consumers must check the round stamp
# ---------------------------------------------------------------------------


class TestServingLint:
    def test_seeded_violation_blind_consumer(self):
        # the exact bug the rule exists for: pull a snapshot, serve its
        # leaves, never look at the round — warm-up garbage and stale
        # models get served silently
        from bluefog_tpu.analysis.serving_lint import (
            check_snapshot_consumers)

        src = (
            "import bluefog_tpu.serving as serving\n"
            "\n"
            "def serve(client, inp):\n"
            "    snap = client.snapshot()\n"
            "    return snap.leaves['x'] @ inp\n"
        )
        diags = check_snapshot_consumers(src, filename="seeded.py")
        assert any(d.code == "BF-SRV001" and d.severity == "error"
                   for d in diags), [d.format() for d in diags]

    def test_round_checked_consumer_is_clean(self):
        from bluefog_tpu.analysis.serving_lint import (
            check_snapshot_consumers)

        src = (
            "import bluefog_tpu.serving as serving\n"
            "\n"
            "def serve(client, inp, cursor):\n"
            "    snap = client.snapshot()\n"
            "    if snap.round <= cursor:\n"
            "        return None\n"
            "    return snap.leaves['x'] @ inp\n"
        )
        assert not check_snapshot_consumers(src, filename="clean.py")

    def test_min_round_kwarg_delegates_the_check(self):
        # min_round=/pin_round= on the call IS the check (the client
        # enforces the bound); no further vocabulary required
        from bluefog_tpu.analysis.serving_lint import (
            check_snapshot_consumers)

        src = (
            "def serve(addr, inp, floor):\n"
            "    c = SnapshotClient(addr, 'job:0')\n"
            "    snap = c.snapshot(min_round=floor)\n"
            "    return snap.leaves['x'] @ inp\n"
        )
        assert not check_snapshot_consumers(src, filename="kwarg.py")

    def test_retriable_handler_counts_as_checking(self):
        from bluefog_tpu.analysis.serving_lint import (
            check_snapshot_consumers)

        src = (
            "import bluefog_tpu.serving as serving\n"
            "\n"
            "def serve(client, inp):\n"
            "    try:\n"
            "        snap = client.snapshot()\n"
            "    except serving.SnapshotUnavailable:\n"
            "        return None\n"
            "    return snap.leaves['x'] @ inp\n"
        )
        assert not check_snapshot_consumers(src, filename="handler.py")

    def test_unrelated_snapshot_apis_not_flagged(self):
        # metrics.export.snapshot() (and anything else named snapshot)
        # is out of scope unless the module imports bluefog_tpu.serving
        # or the receiver is a SnapshotClient
        from bluefog_tpu.analysis.serving_lint import (
            check_snapshot_consumers)

        src = (
            "def export(registry):\n"
            "    return registry.snapshot()\n"
        )
        assert not check_snapshot_consumers(src, filename="metrics.py")

    def test_serving_pass_runs_in_sweep(self):
        # the bflint-tpu sweep includes the serving pass (BF-SRV100
        # info) and reports NO BF-SRV findings on the repo as committed
        report = run_all(size=8, trace=False)
        assert report.has("BF-SRV100"), report.format(verbose=True)
        assert report.ok, report.format()
        assert not [d for d in report.warnings
                    if d.code.startswith("BF-SRV")], report.format()

    def test_round_substring_does_not_suppress(self):
        # 'background'/'workaround' contain 'round' as a substring —
        # they are NOT a round-stamp check and must not silence the rule
        from bluefog_tpu.analysis.serving_lint import (
            check_snapshot_consumers)

        src = (
            "import bluefog_tpu.serving as serving\n"
            "\n"
            "def serve(client, background, workaround):\n"
            "    snap = client.snapshot()\n"
            "    return snap.leaves['x'] + background + workaround\n"
        )
        diags = check_snapshot_consumers(src, filename="substr.py")
        assert any(d.code == "BF-SRV001" for d in diags), \
            [d.format() for d in diags]

    def test_rounds_plural_word_counts(self):
        from bluefog_tpu.analysis.serving_lint import (
            check_snapshot_consumers)

        src = (
            "import bluefog_tpu.serving as serving\n"
            "\n"
            "def serve(client, replica, live):\n"
            "    snap = client.snapshot()\n"
            "    if replica.staleness_rounds(live) > 4:\n"
            "        return None\n"
            "    return snap.leaves['x']\n"
        )
        assert not check_snapshot_consumers(src, filename="plural.py")


# ---------------------------------------------------------------------------
# Pass 8: whole-repo concurrency lint (BF-CONC)
# ---------------------------------------------------------------------------


class TestConcurrencyLint:
    """Each BF-CONC rule must CATCH its seeded violation, honor its
    waiver, stay quiet on the healthy shape — and the repo as committed
    must sweep clean."""

    def _check(self, src, filename="seed.py"):
        from bluefog_tpu.analysis.concurrency_lint import check_sources

        return check_sources([(filename, src)])

    def test_seeded_abba_cycle_is_error(self):
        # the textbook deadlock: two locks nested in opposite orders on
        # two code paths of the same class
        src = (
            "import threading\n"
            "\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "\n"
            "    def fwd(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "\n"
            "    def rev(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n"
        )
        model, diags = self._check(src)
        errs = [d for d in _errors(diags) if d.code == "BF-CONC001"]
        assert errs, [d.format() for d in diags]
        assert "opposite orders" in errs[0].message
        # both edges are in the model, and the cycle names both locks
        assert ("seed.S._a", "seed.S._b") in model.edges
        assert ("seed.S._b", "seed.S._a") in model.edges

    def test_consistent_order_is_clean(self):
        # same two locks, same nesting direction everywhere: no cycle
        src = (
            "import threading\n"
            "\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "\n"
            "    def fwd(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "\n"
            "    def also_fwd(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
        )
        _, diags = self._check(src)
        assert not _errors(diags), [d.format() for d in diags]

    def test_long_cycle_is_not_length_capped(self):
        # a 5-way ring of nestings (a->b->c->d->e->a) deadlocks just
        # like ABBA; the cycle search must not silently cap the length
        src = (
            "import threading\n"
            "\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "        self._c = threading.Lock()\n"
            "        self._d = threading.Lock()\n"
            "        self._e = threading.Lock()\n"
            "\n"
            "    def ab(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "\n"
            "    def bc(self):\n"
            "        with self._b:\n"
            "            with self._c:\n"
            "                pass\n"
            "\n"
            "    def cd(self):\n"
            "        with self._c:\n"
            "            with self._d:\n"
            "                pass\n"
            "\n"
            "    def de(self):\n"
            "        with self._d:\n"
            "            with self._e:\n"
            "                pass\n"
            "\n"
            "    def ea(self):\n"
            "        with self._e:\n"
            "            with self._a:\n"
            "                pass\n"
        )
        model, diags = self._check(src)
        assert len(model.find_cycles()) == 1
        errs = [d for d in _errors(diags) if d.code == "BF-CONC001"]
        assert errs, [d.format() for d in diags]
        assert "opposite orders" in errs[0].message

    def test_self_deadlock_through_helper_is_error(self):
        # the PR-1 engine() shape: a plain Lock re-acquired through a
        # same-module helper called inside the critical section
        src = (
            "import threading\n"
            "\n"
            "_mu = threading.Lock()\n"
            "\n"
            "def helper():\n"
            "    with _mu:\n"
            "        pass\n"
            "\n"
            "def outer():\n"
            "    with _mu:\n"
            "        helper()\n"
        )
        _, diags = self._check(src)
        errs = [d for d in _errors(diags) if d.code == "BF-CONC001"]
        assert errs, [d.format() for d in diags]
        assert "re-acquired" in errs[0].message

    def test_rlock_reentry_is_legal(self):
        src = (
            "import threading\n"
            "\n"
            "_mu = threading.RLock()\n"
            "\n"
            "def helper():\n"
            "    with _mu:\n"
            "        pass\n"
            "\n"
            "def outer():\n"
            "    with _mu:\n"
            "        helper()\n"
        )
        _, diags = self._check(src)
        assert not _errors(diags), [d.format() for d in diags]

    def test_seeded_hold_and_block_is_error(self):
        # blocking socket recv under a lock a daemon worker also takes:
        # a wedged peer parks the worker forever
        src = (
            "import threading\n"
            "\n"
            "class W:\n"
            "    def __init__(self, sock):\n"
            "        self._mu = threading.Lock()\n"
            "        self._sock = sock\n"
            "        t = threading.Thread(target=self._watch, daemon=True)\n"
            "        t.start()\n"
            "\n"
            "    def _watch(self):\n"
            "        with self._mu:\n"
            "            self._beat = 1\n"
            "\n"
            "    def fetch(self):\n"
            "        with self._mu:\n"
            "            return self._sock.recv(4)\n"
        )
        model, diags = self._check(src)
        errs = [d for d in _errors(diags) if d.code == "BF-CONC002"]
        assert errs, [d.format() for d in diags]
        assert "recv" in errs[0].message
        # the model knows WHY: the lock is async-acquired by _watch
        assert "seed:W._watch" in model.async_locks["seed.W._mu"]

    def test_recv_exact_helper_counts_as_blocking(self):
        # the package's wire reads go through the _recv_exact helper,
        # not bare sock.recv — a lock held across it must flag exactly
        # like the raw call (regression: the set once listed the
        # underscore-less name and never matched)
        src = (
            "import threading\n"
            "\n"
            "def _recv_exact(sock, n):\n"
            "    return sock.recv(n)\n"
            "\n"
            "class W:\n"
            "    def __init__(self, sock):\n"
            "        self._mu = threading.Lock()\n"
            "        self._sock = sock\n"
            "        t = threading.Thread(target=self._watch, daemon=True)\n"
            "        t.start()\n"
            "\n"
            "    def _watch(self):\n"
            "        with self._mu:\n"
            "            self._beat = 1\n"
            "\n"
            "    def helper(self):\n"
            "        return _recv_exact(self._sock, 4)\n"
            "\n"
            "    def fetch(self):\n"
            "        with self._mu:\n"
            "            return self.helper()\n"
        )
        _, diags = self._check(src)
        errs = [d for d in _errors(diags) if d.code == "BF-CONC002"]
        assert errs, [d.format() for d in diags]
        assert "_recv_exact" in errs[0].message

    def test_holds_ok_waiver_downgrades_to_info(self):
        src = (
            "import threading\n"
            "\n"
            "class W:\n"
            "    def __init__(self, sock):\n"
            "        self._mu = threading.Lock()\n"
            "        self._sock = sock\n"
            "        t = threading.Thread(target=self._watch, daemon=True)\n"
            "        t.start()\n"
            "\n"
            "    def _watch(self):\n"
            "        with self._mu:\n"
            "            self._beat = 1\n"
            "\n"
            "    def fetch(self):\n"
            "        with self._mu:\n"
            "            return self._sock.recv(4)"
            "  # bfverify: holds-ok reviewed ack fence\n"
        )
        _, diags = self._check(src)
        assert not _errors(diags), [d.format() for d in diags]
        waived = [d for d in diags if d.code == "BF-CONC002W"]
        assert waived and "reviewed ack fence" in waived[0].message

    def test_bare_waiver_without_reason_waives_nothing(self):
        # a reasonless token must NOT suppress the finding
        src = (
            "import threading\n"
            "\n"
            "class W:\n"
            "    def __init__(self, sock):\n"
            "        self._mu = threading.Lock()\n"
            "        self._sock = sock\n"
            "        t = threading.Thread(target=self._watch, daemon=True)\n"
            "        t.start()\n"
            "\n"
            "    def _watch(self):\n"
            "        with self._mu:\n"
            "            self._beat = 1\n"
            "\n"
            "    def fetch(self):\n"
            "        with self._mu:\n"
            "            return self._sock.recv(4)  # bfverify: holds-ok\n"
        )
        _, diags = self._check(src)
        assert any(d.code == "BF-CONC002" for d in _errors(diags)), \
            [d.format() for d in diags]

    def test_timed_blocking_call_is_exempt(self):
        # an explicit timeout= bounds the call: connect-with-deadline
        # under a shared lock is a latency bug at worst, not a wedge —
        # the same call with no deadline still flags
        base = (
            "import socket\n"
            "import threading\n"
            "\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        t = threading.Thread(target=self._watch, daemon=True)\n"
            "        t.start()\n"
            "\n"
            "    def _watch(self):\n"
            "        with self._mu:\n"
            "            self._beat = 1\n"
            "\n"
            "    def dial(self, addr):\n"
            "        with self._mu:\n"
            "            return socket.create_connection(%s)\n"
        )
        _, diags = self._check(base % "addr, timeout=5.0")
        assert not [d for d in _errors(diags) if d.code == "BF-CONC002"], \
            [d.format() for d in diags]
        _, diags = self._check(base % "addr")
        assert [d for d in _errors(diags) if d.code == "BF-CONC002"], \
            [d.format() for d in diags]

    def test_blocking_without_shared_lock_is_clean(self):
        # blocking under a lock NO async context touches: fine (the
        # only waiter is another synchronous caller of the same API)
        src = (
            "import threading\n"
            "\n"
            "class W:\n"
            "    def __init__(self, sock):\n"
            "        self._mu = threading.Lock()\n"
            "        self._sock = sock\n"
            "\n"
            "    def fetch(self):\n"
            "        with self._mu:\n"
            "            return self._sock.recv(4)\n"
        )
        _, diags = self._check(src)
        assert not _errors(diags), [d.format() for d in diags]

    def test_seeded_unlocked_shared_attr_is_warning(self):
        src = (
            "import threading\n"
            "\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.count = 0\n"
            "        t = threading.Thread(target=self._run, daemon=True)\n"
            "        t.start()\n"
            "\n"
            "    def _run(self):\n"
            "        self.count = 1\n"
            "\n"
            "    def read(self):\n"
            "        return self.count\n"
        )
        _, diags = self._check(src)
        hits = [d for d in diags if d.code == "BF-CONC003"]
        assert hits and hits[0].severity == "warning", \
            [d.format() for d in diags]
        assert "count" in hits[0].message

    def test_common_lock_silences_shared_attr(self):
        src = (
            "import threading\n"
            "\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self.count = 0\n"
            "        t = threading.Thread(target=self._run, daemon=True)\n"
            "        t.start()\n"
            "\n"
            "    def _run(self):\n"
            "        with self._mu:\n"
            "            self.count = 1\n"
            "\n"
            "    def read(self):\n"
            "        with self._mu:\n"
            "            return self.count\n"
        )
        _, diags = self._check(src)
        assert not any(d.code == "BF-CONC003" for d in diags), \
            [d.format() for d in diags]

    def test_shared_ok_waiver_honored(self):
        src = (
            "import threading\n"
            "\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.count = 0\n"
            "        t = threading.Thread(target=self._run, daemon=True)\n"
            "        t.start()\n"
            "\n"
            "    def _run(self):\n"
            "        self.count = 1"
            "  # bfverify: shared-ok GIL-atomic int store\n"
            "\n"
            "    def read(self):\n"
            "        return self.count\n"
        )
        _, diags = self._check(src)
        assert not any(d.code == "BF-CONC003" for d in diags), \
            [d.format() for d in diags]

    def test_condvar_wait_outside_while_is_info(self):
        src = (
            "import threading\n"
            "\n"
            "class P:\n"
            "    def __init__(self):\n"
            "        self._cv = threading.Condition()\n"
            "\n"
            "    def get(self):\n"
            "        with self._cv:\n"
            "            self._cv.wait()\n"
        )
        _, diags = self._check(src)
        hits = [d for d in diags if d.code == "BF-CONC010"]
        assert hits and hits[0].severity == "info", \
            [d.format() for d in diags]

    def test_condvar_wait_in_while_is_clean(self):
        src = (
            "import threading\n"
            "\n"
            "class P:\n"
            "    def __init__(self):\n"
            "        self._cv = threading.Condition()\n"
            "        self._ready = False\n"
            "\n"
            "    def get(self):\n"
            "        with self._cv:\n"
            "            while not self._ready:\n"
            "                self._cv.wait()\n"
        )
        _, diags = self._check(src)
        assert not any(d.code == "BF-CONC010" for d in diags), \
            [d.format() for d in diags]

    def test_condition_aliases_its_underlying_lock(self):
        # Condition(existing_lock) is ONE ordering identity with it —
        # cv-nested-under-its-own-lock must not fabricate an edge
        src = (
            "import threading\n"
            "\n"
            "class T:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._cv = threading.Condition(self._mu)\n"
        )
        model, _ = self._check(src)
        cv = model.locks["seed.T._cv"]
        assert model.resolve_alias("seed.T._cv") == "seed.T._mu", cv

    def test_repo_sweeps_clean(self):
        # the acceptance bar: every BF-CONC001/002 on the tree is fixed
        # or carries a reasoned waiver; warnings triaged to zero
        from bluefog_tpu.analysis.concurrency_lint import check_package

        model, diags = check_package()
        assert not _errors(diags), [d.format() for d in diags]
        assert not [d for d in diags if d.severity == "warning"], \
            [d.format() for d in diags]
        # the model actually saw the runtime (not an empty scan)
        assert len(model.locks) >= 30, len(model.locks)
        assert model.thread_entries, "no thread entry points found?"

    def test_concurrency_pass_runs_in_sweep(self):
        from bluefog_tpu.analysis.lint import concurrency_pass

        report = LintReport()
        concurrency_pass(report, 4)
        assert report.has("BF-CONC100"), report.format(verbose=True)
        assert report.ok, report.format()

    def test_bfverify_cli_exits_zero(self):
        # the standalone CLI over the repo as committed: graph + tables
        # print, no error findings survive, exit 0
        proc = subprocess.run(
            [sys.executable, "-m",
             "bluefog_tpu.analysis.concurrency_lint", "--dot", "-"],
            capture_output=True, text=True, timeout=120,
            cwd=REPO, env=clean_env())
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "bfverify: OK" in proc.stdout
        assert "digraph lock_order" in proc.stdout
        assert "lock-order edges" in proc.stdout


class TestShardingLint:
    """BF-SHD: the unified rule table vs the leaf families it governs —
    coverage leaks (001), window-declaration drift (002), and a gather
    on the gossip hot path (003, by jaxpr inspection)."""

    def _tree(self):
        return {"blk": {"up": {"kernel": jnp.zeros((4, 8)),
                               "bias": jnp.zeros((8,))},
                        "ln": {"count": jnp.zeros(())}}}

    def test_seeded_violation_unmatched_leaf(self):
        from bluefog_tpu.analysis.sharding_lint import check_rule_coverage
        from bluefog_tpu.sharding import RuleTable

        table = RuleTable([("kernel$", P(None, "tp"))])  # no catch-all
        diags = check_rule_coverage(table, self._tree())
        errs = _errors(diags)
        assert errs and all(d.code == "BF-SHD001" for d in errs)
        assert any("up/bias" in d.message for d in errs)
        # the scalar is exempt — it resolves replicated, not leaked
        assert not any("count" in d.message for d in errs)

    def test_seeded_violation_dead_rule(self):
        from bluefog_tpu.analysis.sharding_lint import check_rule_coverage
        from bluefog_tpu.sharding import RuleTable

        table = RuleTable([("typod_pattern$", P("tp")), (".*", P())])
        diags = check_rule_coverage(table, self._tree())
        assert any(d.code == "BF-SHD001" and "typod_pattern" in d.message
                   for d in _errors(diags))

    def test_clean_coverage(self):
        from bluefog_tpu.analysis.sharding_lint import check_rule_coverage
        from bluefog_tpu.sharding import RuleTable

        table = RuleTable([("kernel$", P(None, "tp")), (".*", P())])
        assert not check_rule_coverage(table, self._tree())

    def test_seeded_violation_window_declaration_drift(self):
        from bluefog_tpu.analysis.sharding_lint import (
            check_window_partition)
        from bluefog_tpu.ops.windows import win_create
        from bluefog_tpu.sharding import RuleTable

        created_under = RuleTable([("kernel$", P(None, "tp")), (".*", P())])
        live = RuleTable([("kernel$", P("tp", None)), (".*", P())])
        sched = T.build_schedule(T.RingGraph(4))
        win = win_create(self._tree(), sched, AXIS,
                         rule_table=created_under)
        diags = check_window_partition(win, live)
        assert any(d.code == "BF-SHD002" and "kernel" in d.message
                   for d in diags)
        # same table -> clean
        assert not check_window_partition(win, created_under)
        # undeclared (legacy) window -> the one-shot warning
        legacy = win_create(self._tree(), sched, AXIS)
        diags = check_window_partition(legacy, live)
        assert [d.code for d in diags] == ["BF-SHD002"]
        assert "declares no partition" in diags[0].message

    def test_seeded_violation_gather_on_hot_path(self, devices8):
        from bluefog_tpu.analysis.sharding_lint import check_shard_local
        from bluefog_tpu.parallel.tensor import make_hybrid_mesh

        mesh = make_hybrid_mesh({"bf": 4, "tp": 2}, devices=devices8)

        def gathers(x):
            return lax.all_gather(x, "tp", tiled=True)

        fn = shard_map(gathers, mesh=mesh, in_specs=(P("tp"),),
                       out_specs=P(), check_vma=False)
        diags = check_shard_local(fn, jnp.zeros((8,)),
                                  inner_axes={"tp": 2})
        assert any(d.code == "BF-SHD003" for d in _errors(diags))

    def test_clean_sharded_gossip_step(self, devices8):
        from bluefog_tpu.analysis.sharding_lint import check_shard_local
        from bluefog_tpu.parallel.tensor import make_hybrid_mesh
        from bluefog_tpu.sharding import RuleTable

        mesh = make_hybrid_mesh({"bf": 4, "tp": 2}, devices=devices8)
        sched = T.build_schedule(T.RingGraph(4))
        table = RuleTable([("w$", P(None, "tp")), (".*", P())])

        def step(x):
            return C.sharded_neighbor_allreduce(
                x, sched, AXIS, rule_table=table, inner_axes={"tp": 2})

        fn = shard_map(step, mesh=mesh,
                       in_specs=({"w": P("bf", "tp")},),
                       out_specs={"w": P("bf", "tp")}, check_vma=False)
        diags = check_shard_local(fn, {"w": jnp.zeros((4, 8))},
                                  inner_axes={"tp": 2})
        assert not _errors(diags), [d.format() for d in diags]
        assert any(d.code == "BF-SHD103" for d in diags)

    def test_trace_failure_is_a_finding(self):
        from bluefog_tpu.analysis.sharding_lint import check_shard_local

        def boom(x):
            raise RuntimeError("no trace for you")

        diags = check_shard_local(boom, jnp.zeros((4,)),
                                  inner_axes={"tp": 2})
        assert [d.code for d in diags] == ["BF-SHD020"]

    def test_repo_sharding_pass_clean(self):
        """The sweep's own pass over the repo's default tables finds no
        errors (repo-clean)."""
        from bluefog_tpu.analysis import lint as L

        report = LintReport()
        L.sharding_pass(report, 8)
        errs = [d for d in report.diagnostics if d.severity == "error"]
        assert not errs, [d.format() for d in errs]
        assert any(d.code == "BF-SHD100" for d in report.diagnostics)


class TestTracingLint:
    """BF-TRC001: an explicit begin_span without a finally-guaranteed
    finish (or a reasoned cross-thread waiver) leaks a forever-open
    span — a completed phase then reads as wedged."""

    def test_seeded_violation_unguarded_begin(self):
        from bluefog_tpu.analysis.tracing_lint import check_span_discharge

        src = (
            "def send(rec, sock, data):\n"
            "    sp = rec.begin_span('wire', 'tcp')\n"
            "    sock.sendall(data)\n"
            "    sp.finish()\n"  # skipped when sendall raises
        )
        diags = check_span_discharge(src, filename="seeded.py")
        assert any(d.code == "BF-TRC001" and d.severity == "error"
                   for d in diags), [d.format() for d in diags]

    def test_finally_guarded_begin_is_clean(self):
        from bluefog_tpu.analysis.tracing_lint import check_span_discharge

        src = (
            "def send(rec, sock, data):\n"
            "    sp = rec.begin_span('wire', 'tcp')\n"
            "    try:\n"
            "        sock.sendall(data)\n"
            "    finally:\n"
            "        sp.finish()\n"
        )
        assert not check_span_discharge(src, filename="clean.py")

    def test_cross_thread_waiver_needs_a_reason(self):
        from bluefog_tpu.analysis.tracing_lint import check_span_discharge

        waived = (
            "def send(rec):\n"
            "    sp = rec.begin_span(  # bftrace: cross-thread ack "
            "reader finishes it\n"
            "        'wire', 'tcp')\n"
        )
        assert not check_span_discharge(waived, filename="waived.py")
        bare = (
            "def send(rec):\n"
            "    sp = rec.begin_span('wire')  # bftrace: cross-thread\n"
        )
        diags = check_span_discharge(bare, filename="bare.py")
        assert any(d.code == "BF-TRC001" for d in diags), \
            "a waiver without a reason must still be an error"

    def test_nested_function_judged_against_its_own_body(self):
        from bluefog_tpu.analysis.tracing_lint import check_span_discharge

        # the OUTER function's try/finally must not excuse a begin
        # inside a nested def that has no guard of its own
        src = (
            "def outer(rec):\n"
            "    def worker():\n"
            "        sp = rec.begin_span('apply')\n"
            "        sp.finish()\n"
            "    try:\n"
            "        worker()\n"
            "    finally:\n"
            "        rec.flush().finish()\n"
        )
        diags = check_span_discharge(src, filename="nested.py")
        assert any(d.code == "BF-TRC001" for d in diags), \
            [d.format() for d in diags]

    def test_nested_guard_cannot_vouch_for_outer_begin(self):
        from bluefog_tpu.analysis.tracing_lint import check_span_discharge

        # the reverse false negative: a finally-finish inside a nested
        # helper must not excuse the OUTER function's leaked begin
        src = (
            "def outer(rec, other):\n"
            "    sp = rec.begin_span('wire')\n"
            "    def helper():\n"
            "        try:\n"
            "            pass\n"
            "        finally:\n"
            "            other.finish()\n"
            "    helper()\n"
        )
        diags = check_span_discharge(src, filename="vouch.py")
        assert any(d.code == "BF-TRC001" for d in diags), \
            [d.format() for d in diags]

    def test_module_level_begin_is_error(self):
        from bluefog_tpu.analysis.tracing_lint import check_span_discharge

        diags = check_span_discharge("sp = rec.begin_span('x')\n",
                                     filename="mod.py")
        assert any(d.code == "BF-TRC001" for d in diags)

    def test_span_context_manager_is_never_flagged(self):
        from bluefog_tpu.analysis.tracing_lint import check_span_discharge

        src = (
            "def round_(rec):\n"
            "    with rec.span('gossip', 'dsgd'):\n"
            "        pass\n"
        )
        assert not check_span_discharge(src, filename="cm.py")

    def test_repo_tracing_pass_clean(self):
        """The standard sweep's tracing pass over the repo itself:
        every real begin_span is guarded or carries a reasoned
        cross-thread waiver."""
        from bluefog_tpu.analysis import lint as L

        report = LintReport()
        L.tracing_pass(report, 8)
        errs = [d for d in report.diagnostics if d.severity == "error"]
        assert not errs, [d.format() for d in errs]
        assert any(d.code == "BF-TRC100" for d in report.diagnostics)


class TestDocLint:
    def test_repo_doc_matches_registry(self):
        from bluefog_tpu.analysis.doc_lint import check_transport_doc

        diags = check_transport_doc()
        assert not _errors(diags), [d.format() for d in diags]

    def test_missing_code_is_error(self, tmp_path):
        from bluefog_tpu.analysis.doc_lint import check_transport_doc
        from bluefog_tpu.runtime import wire_status as ws

        doc = tmp_path / "transport.md"
        codes = [c for c in ws.WIRE_V2_CODES if c != ws.ERR_BUSY]
        doc.write_text("status codes: " +
                       ", ".join(str(c) for c in codes) + "\n")
        diags = check_transport_doc(str(doc))
        errs = [d for d in _errors(diags) if d.code == "BF-DOC001"]
        assert errs and str(ws.ERR_BUSY) in errs[0].message, \
            [d.format() for d in diags]

    def test_stray_doc_code_is_error(self, tmp_path):
        from bluefog_tpu.analysis.doc_lint import check_transport_doc
        from bluefog_tpu.runtime import wire_status as ws

        doc = tmp_path / "transport.md"
        codes = list(ws.WIRE_V2_CODES) + [-199]
        doc.write_text("status codes: " +
                       ", ".join(str(c) for c in codes) + "\n")
        diags = check_transport_doc(str(doc))
        errs = [d for d in _errors(diags) if d.code == "BF-DOC001"]
        assert errs and "-199" in errs[0].message, \
            [d.format() for d in diags]

    def test_unassigned_gap_is_tolerated(self, tmp_path):
        # the doc may (should) mention the deliberately-unassigned -103
        from bluefog_tpu.analysis.doc_lint import check_transport_doc
        from bluefog_tpu.runtime import wire_status as ws

        doc = tmp_path / "transport.md"
        codes = list(ws.WIRE_V2_CODES) + list(ws.UNASSIGNED_CODES)
        doc.write_text("status codes: " +
                       ", ".join(str(c) for c in codes) + "\n")
        assert not _errors(check_transport_doc(str(doc)))

    # -------------------------------------------------- BF-DOC002 (metrics)
    def test_repo_metrics_doc_matches_live_names(self):
        """Both directions clean on the repo itself — every emitted
        bf_* metric has a doc row and no doc row is stale."""
        from bluefog_tpu.analysis.doc_lint import check_metrics_doc

        diags = check_metrics_doc()
        assert not _errors(diags), [d.format() for d in diags]
        assert any(d.code == "BF-DOC101" for d in diags)

    @staticmethod
    def _metric_src_tree(tmp_path, body: str):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(body)
        return str(pkg)

    def test_undocumented_metric_is_error(self, tmp_path):
        from bluefog_tpu.analysis.doc_lint import check_metrics_doc

        src = self._metric_src_tree(
            tmp_path,
            "def f(reg):\n"
            "    reg.counter('bf_documented_total').inc()\n"
            "    reg.gauge('bf_renamed_new_name').set(1.0)\n")
        doc = tmp_path / "metrics.md"
        doc.write_text("| `bf_documented_total` | counter |\n")
        errs = [d for d in _errors(check_metrics_doc(str(doc), src))
                if d.code == "BF-DOC002"]
        assert len(errs) == 1
        assert "bf_renamed_new_name" in errs[0].message

    def test_stale_doc_row_is_error(self, tmp_path):
        """The renamed-metric drift the sweep previously missed: the
        old name's doc row survives the rename."""
        from bluefog_tpu.analysis.doc_lint import check_metrics_doc

        src = self._metric_src_tree(
            tmp_path,
            "def f(reg):\n"
            "    reg.counter('bf_new_name_total').inc()\n")
        doc = tmp_path / "metrics.md"
        doc.write_text("| `bf_new_name_total` | counter |\n"
                       "| `bf_old_name_total` | counter |\n")
        errs = [d for d in _errors(check_metrics_doc(str(doc), src))
                if d.code == "BF-DOC002"]
        assert len(errs) == 1
        assert "bf_old_name_total" in errs[0].message

    def test_hist_expansion_spelling_normalizes(self, tmp_path):
        """A doc that spells `bf_x_seconds_p99` documents the
        histogram `bf_x_seconds`, and an FFI-style bf_* literal
        outside a metric call is not a metric."""
        from bluefog_tpu.analysis.doc_lint import check_metrics_doc

        src = self._metric_src_tree(
            tmp_path,
            "def f(reg, lib):\n"
            "    reg.histogram('bf_x_seconds').observe(0.1)\n"
            "    lib.symbol('bf_win_create')\n"
            "    count(None, [('bf_tuple_total', 1)])\n")
        doc = tmp_path / "metrics.md"
        doc.write_text("rows: `bf_x_seconds_p99`, `bf_tuple_total`\n")
        diags = check_metrics_doc(str(doc), src)
        assert not _errors(diags), [d.format() for d in diags]


class TestFleetLint:
    """BF-FLT001: an alert/SLO threshold without its hysteresis twin or
    a declared window is an error — the ControlConfig discipline
    applied to the fleet plane's spec sites."""

    def test_seeded_violation_enter_without_exit(self):
        from bluefog_tpu.analysis.fleet_lint import check_slo_specs

        src = ("spec = SLOSpec(name='x', signal='round_p99_s',\n"
               "               warn_enter=1.0, window=4)\n")
        diags = check_slo_specs(src, filename="seeded.py")
        assert any(d.code == "BF-FLT001" and d.severity == "error"
                   and "warn_exit" in d.message for d in diags), \
            [d.format() for d in diags]

    def test_seeded_violation_no_window(self):
        from bluefog_tpu.analysis.fleet_lint import check_slo_specs

        src = ("spec = SLOSpec(name='x', signal='round_p99_s',\n"
               "               warn_enter=1.0, warn_exit=0.5)\n")
        diags = check_slo_specs(src, filename="seeded2.py")
        assert any(d.code == "BF-FLT001" and "window" in d.message
                   for d in diags), [d.format() for d in diags]

    def test_seeded_violation_bare_threshold(self):
        from bluefog_tpu.analysis.fleet_lint import check_slo_specs

        src = "rule = AlertRule(threshold=5, window=4)\n"
        diags = check_slo_specs(src, filename="seeded3.py")
        assert any(d.code == "BF-FLT001" and "threshold" in d.message
                   for d in diags), [d.format() for d in diags]

    def test_full_spec_and_unrelated_calls_clean(self):
        from bluefog_tpu.analysis.fleet_lint import check_slo_specs

        src = (
            "spec = SLOSpec(name='x', signal='round_p99_s',\n"
            "               warn_enter=1.0, warn_exit=0.5, window=4,\n"
            "               page_enter=4.0, page_exit=2.0)\n"
            # alert-ish names with no threshold kwargs are fine
            "eng = SLOEngine((spec,), rank=3)\n"
            "ctl.note_alert(2, suspect=True)\n"
            # non-alert calls with enter-style kwargs are out of scope
            "cfg = ControlConfig(slow_enter=4.0)\n"
        )
        assert not check_slo_specs(src, filename="clean.py")

    def test_positional_form_left_to_runtime(self):
        from bluefog_tpu.analysis.fleet_lint import check_slo_specs

        # positional/config-dict spellings are the runtime validator's
        # job (SLOSpec.__post_init__ raises on unpaired thresholds)
        src = "spec = SLOSpec('x', 'round_p99_s', 1.0, 0.5, 4)\n"
        assert not check_slo_specs(src, filename="positional.py")

    def test_fleet_package_is_repo_clean(self):
        import glob

        from bluefog_tpu.analysis.fleet_lint import check_file

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        errs = []
        for pat in ("bluefog_tpu/fleet/*.py", "bluefog_tpu/runtime/*.py",
                    "examples/*.py", "benchmarks/*.py"):
            for path in glob.glob(os.path.join(root, pat)):
                errs += [d for d in check_file(path)
                         if d.severity == "error"]
        assert not errs, [d.format() for d in errs]


class TestSimLint:
    """BF-SIM001: the simulator's determinism contract (no wall clock,
    no ambient RNG inside bluefog_tpu/sim/) and the scenario-table
    discipline (every Scenario(...) call site declares accept= and a
    bounded horizon_s=)."""

    def test_seeded_wall_clock_violation(self):
        from bluefog_tpu.analysis.sim_lint import check_determinism

        src = "import time\ndef handler():\n    return time.time()\n"
        diags = check_determinism(src, filename="seeded_sim.py")
        assert any(d.code == "BF-SIM001" and d.severity == "error"
                   and "VIRTUAL clock" in d.message for d in diags), \
            [d.format() for d in diags]

    def test_seeded_ambient_rng_violation(self):
        from bluefog_tpu.analysis.sim_lint import check_determinism

        src = ("import random\nimport numpy as np\n"
               "a = random.random()\nb = np.random.rand(3)\n")
        diags = check_determinism(src, filename="seeded_sim2.py")
        assert sum(1 for d in diags if d.code == "BF-SIM001") == 2, \
            [d.format() for d in diags]

    def test_seeded_generators_are_clean(self):
        from bluefog_tpu.analysis.sim_lint import check_determinism

        src = ("import random\nimport numpy as np\n"
               "r = random.Random(7)\nv = r.random()\n"
               "g = np.random.default_rng(7)\n")
        assert not check_determinism(src, filename="clean_sim.py")

    def test_scenario_missing_accept_or_horizon(self):
        from bluefog_tpu.analysis.sim_lint import check_scenario_table

        src = ("s = Scenario(name='x', kind='fleet', n_ranks=8,\n"
               "             horizon_s=1.0)\n"
               "t = Scenario(name='y', kind='fleet', n_ranks=8,\n"
               "             accept=(('audit_exact', {}),))\n")
        diags = check_scenario_table(src, filename="seeded_sc.py")
        msgs = [d.message for d in diags if d.code == "BF-SIM001"]
        assert any("accept=" in m for m in msgs), msgs
        assert any("horizon_s=" in m for m in msgs), msgs

    def test_scenario_splat_left_to_runtime(self):
        from bluefog_tpu.analysis.sim_lint import check_scenario_table

        # **kwargs spellings are the runtime validator's job
        # (Scenario.__post_init__ raises on a missing accept/horizon)
        src = "s = Scenario(**cfg)\n"
        assert not check_scenario_table(src, filename="splat.py")

    def test_determinism_rule_scoped_to_sim_package(self):
        from bluefog_tpu.analysis.sim_lint import check_file

        # a wall-clock call OUTSIDE bluefog_tpu/sim/ is not this
        # lint's business (the fleet publisher reads time.time by
        # design); only the scenario-table rule applies there
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = os.path.join(root, "bluefog_tpu", "fleet", "record.py")
        assert not [d for d in check_file(path) if d.severity == "error"]

    def test_sim_package_is_repo_clean(self):
        import glob

        from bluefog_tpu.analysis.sim_lint import check_file

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        errs = []
        for pat in ("bluefog_tpu/sim/*.py", "examples/*.py",
                    "benchmarks/*.py"):
            for path in glob.glob(os.path.join(root, pat)):
                errs += [d for d in check_file(path)
                         if d.severity == "error"]
        assert not errs, [d.format() for d in errs]


# ---------------------------------------------------------------------------
# Pass 13: wire-protocol verifier (bfwire-tpu)
# ---------------------------------------------------------------------------


class TestWireLint:
    """BF-WIRE001..004 on synthetic sources (one seeded + one clean per
    code), the waiver grammar, the registry staleness satellite, and
    the repo-clean sweep.  The state-machine layer (BF-WIRE005) has its
    own conformance suite in tests/test_wire_verify.py."""

    @staticmethod
    def _check(*sources):
        from bluefog_tpu.analysis.protocol_check import check_sources

        return check_sources(list(sources))

    # ------------------------------------------------ BF-WIRE001 (layout)
    def test_conflicting_struct_formats_caught(self):
        _, diags = self._check(
            ("a.py", "import struct\n_FRAME = struct.Struct('<Iq')\n"),
            ("b.py", "import struct\n_FRAME = struct.Struct('<IqB')\n"))
        errs = [d for d in _errors(diags) if d.code == "BF-WIRE001"]
        assert errs and "CONFLICTING" in errs[0].message, \
            [d.format() for d in diags]

    def test_packed_never_unpacked_caught(self):
        _, diags = self._check(("a.py", (
            "import struct\n"
            "_ONLY = struct.Struct('<q')\n"
            "def emit(sock, n):\n"
            "    sock.sendall(_ONLY.pack(n))\n")))
        errs = [d for d in _errors(diags) if d.code == "BF-WIRE001"]
        assert errs and "no protocol module ever unpacks" in \
            errs[0].message, [d.format() for d in diags]

    def test_inline_struct_call_caught(self):
        _, diags = self._check(("a.py", (
            "import struct\n"
            "def emit(sock, n):\n"
            "    sock.sendall(struct.pack('<q', n))\n")))
        errs = [d for d in _errors(diags) if d.code == "BF-WIRE001"]
        assert errs and "hand-rolled" in errs[0].message

    def test_per_op_imbalance_caught(self):
        # op 0 packs _REQ; the decode side unpacks it only under op 1 —
        # the other side of the frame drifted to a different dispatch
        _, diags = self._check(("a.py", (
            "import struct\n"
            "_MAGIC = 7\n"
            "_OP_A = 0\n"
            "_OP_B = 1\n"
            "_HDR = struct.Struct('<IBH')\n"
            "_REQ = struct.Struct('<q')\n"
            "def send(sock, n):\n"
            "    sock.sendall(_HDR.pack(_MAGIC, _OP_A, 0)"
            " + _REQ.pack(n))\n"
            "def handle(sock, op, payload):\n"
            "    magic, op, nl = _HDR.unpack(payload)\n"
            "    if op == _OP_B:\n"
            "        (x,) = _REQ.unpack(payload)\n")))
        errs = [d for d in _errors(diags) if d.code == "BF-WIRE001"]
        assert any("op 0 packs struct _REQ" in d.message for d in errs), \
            [d.format() for d in diags]

    def test_balanced_ops_clean(self):
        _, diags = self._check(("a.py", (
            "import struct\n"
            "_MAGIC = 7\n"
            "_OP_A = 0\n"
            "_HDR = struct.Struct('<IBH')\n"
            "_REQ = struct.Struct('<q')\n"
            "def send(sock, n):\n"
            "    sock.sendall(_HDR.pack(_MAGIC, _OP_A, 0)"
            " + _REQ.pack(n))\n"
            "def handle(sock, op, payload):\n"
            "    magic, op, nl = _HDR.unpack(payload)\n"
            "    if op == _OP_A:\n"
            "        (x,) = _REQ.unpack(payload)\n")))
        assert not [d for d in _errors(diags) if d.code == "BF-WIRE001"]

    # ------------------------------------------------------ waiver grammar
    def test_reasoned_waiver_downgrades_to_info(self):
        _, diags = self._check(("a.py", (
            "import struct\n"
            "# bfwire: layout-ok decoder lives in the relay binary\n"
            "_ONLY = struct.Struct('<q')\n"
            "def emit(sock, n):\n"
            "    sock.sendall(_ONLY.pack(n))\n")))
        assert not [d for d in _errors(diags) if d.code == "BF-WIRE001"]
        infos = [d for d in diags if d.code == "BF-WIRE001W"]
        assert infos and "relay binary" in infos[0].message

    def test_bare_waiver_token_waives_nothing(self):
        _, diags = self._check(("a.py", (
            "import struct\n"
            "# bfwire: layout-ok\n"
            "_ONLY = struct.Struct('<q')\n"
            "def emit(sock, n):\n"
            "    sock.sendall(_ONLY.pack(n))\n")))
        assert [d for d in _errors(diags) if d.code == "BF-WIRE001"]

    # ------------------------------------------------ BF-WIRE002 (status)
    def test_unregistered_status_literal_caught(self):
        # no registry in the synthetic source: the live wire_status
        # table is the fallback ground truth, and -142 is not in it
        _, diags = self._check(("a.py", (
            "def reply(self, sock):\n"
            "    self._send_status(-142)\n")))
        errs = [d for d in _errors(diags) if d.code == "BF-WIRE002"]
        assert errs and "-142" in errs[0].message

    def test_registered_status_emit_clean(self):
        _, diags = self._check(("a.py", (
            "def reply(self, sock):\n"
            "    self._send_status(-106)\n")))
        assert not [d for d in _errors(diags) if d.code == "BF-WIRE002"]

    def test_retriable_code_raised_terminal_caught(self):
        _, diags = self._check(("a.py", (
            "_ERR_BUSY = -106\n"
            "_RETRIABLE = frozenset({_ERR_BUSY})\n"
            "def check(rc):\n"
            "    if rc == _ERR_BUSY:\n"
            "        raise RuntimeError('busy')\n")))
        errs = [d for d in _errors(diags) if d.code == "BF-WIRE002"]
        assert errs and "RETRIABLE per wire_status" in errs[0].message

    def test_terminal_code_raised_retriable_caught(self):
        _, diags = self._check(("a.py", (
            "_ERR_GONE = -105\n"
            "def check(rc):\n"
            "    if rc == _ERR_GONE:\n"
            "        raise ConnectionError('retry?')\n")))
        errs = [d for d in _errors(diags) if d.code == "BF-WIRE002"]
        assert errs and "TERMINAL per wire_status" in errs[0].message

    def test_matching_handling_clean(self):
        _, diags = self._check(("a.py", (
            "_ERR_BUSY = -106\n"
            "_RETRIABLE = frozenset({_ERR_BUSY})\n"
            "def check(rc):\n"
            "    if rc == _ERR_BUSY:\n"
            "        raise ConnectionError('backing off')\n")))
        assert not [d for d in _errors(diags) if d.code == "BF-WIRE002"]

    def test_stale_unassigned_codes_caught(self):
        from bluefog_tpu.analysis.protocol_check import check_registry

        diags = check_registry(codes=(-100, -101, -104), unassigned=())
        assert diags and diags[0].code == "BF-WIRE002"
        assert "-102" in diags[0].message and "-103" in diags[0].message
        # the live registry's gap list is generated, hence never stale
        assert not check_registry()

    # ------------------------------------------------- BF-WIRE003 (gates)
    _GATE_PRELUDE = ("import struct\n"
                     "_MAGIC = 7\n"
                     "_OP_STREAM_ATTACH = 6\n"
                     "_HDR = struct.Struct('<IBH')\n")

    def test_ungated_feature_op_caught(self):
        _, diags = self._check(("a.py", self._GATE_PRELUDE + (
            "def attach(sock):\n"
            "    sock.sendall(_HDR.pack(_MAGIC, _OP_STREAM_ATTACH, 0))\n"
        )))
        errs = [d for d in _errors(diags) if d.code == "BF-WIRE003"]
        assert errs and "FEATURE_RESUME" in errs[0].message

    def test_gate_evidence_in_scope_clean(self):
        _, diags = self._check(("a.py", self._GATE_PRELUDE + (
            "def attach(sock, granted):\n"
            "    if granted & FEATURE_RESUME:\n"
            "        sock.sendall(_HDR.pack(_MAGIC,"
            " _OP_STREAM_ATTACH, 0))\n")))
        assert not [d for d in _errors(diags) if d.code == "BF-WIRE003"]

    def test_gate_ok_waiver_downgrades_to_info(self):
        _, diags = self._check(("a.py", self._GATE_PRELUDE + (
            "def attach(sock):\n"
            "    # bfwire: gate-ok caller negotiated the bit\n"
            "    sock.sendall(_HDR.pack(_MAGIC, _OP_STREAM_ATTACH, 0))\n"
        )))
        assert not [d for d in _errors(diags) if d.code == "BF-WIRE003"]
        assert any(d.code == "BF-WIRE003W" for d in diags)

    # ------------------------------------------------ BF-WIRE004 (bounds)
    _BOUND_PRELUDE = ("import struct\n"
                      "import numpy as np\n"
                      "_MAX_BLOB = 1024\n"
                      "_CNT = struct.Struct('<q')\n"
                      "def send(sock, n):\n"
                      "    sock.sendall(_CNT.pack(n))\n")

    def test_unguarded_wire_length_caught(self):
        _, diags = self._check(("a.py", self._BOUND_PRELUDE + (
            "def read(sock):\n"
            "    (n,) = _CNT.unpack(_recv_exact(sock, 8))\n"
            "    return np.empty(n)\n")))
        errs = [d for d in _errors(diags) if d.code == "BF-WIRE004"]
        assert errs and "'n'" in errs[0].message and \
            "np" not in errs[0].subject

    def test_bounded_wire_length_clean(self):
        _, diags = self._check(("a.py", self._BOUND_PRELUDE + (
            "def read(sock):\n"
            "    (n,) = _CNT.unpack(_recv_exact(sock, 8))\n"
            "    if n < 0 or n > _MAX_BLOB:\n"
            "        raise ValueError('bad frame')\n"
            "    return np.empty(n)\n")))
        assert not [d for d in _errors(diags) if d.code == "BF-WIRE004"]

    # --------------------------------------------------------- repo sweep
    def test_repo_protocol_surface_is_clean(self):
        from bluefog_tpu.analysis.protocol_check import check_package

        model, diags = check_package()
        assert not _errors(diags), [d.format() for d in _errors(diags)]
        assert any(d.code == "BF-WIRE100" for d in diags)
        assert any(d.code == "BF-WIRE101" for d in diags)
        # the triaged waivers surface as infos, never silently
        assert any(d.code == "BF-WIRE001W" for d in diags)
        # the model actually covers the protocol surface
        assert len(model.files) == 7
        assert model.structs and model.uses and model.status_sites

    def test_cli_exits_zero_on_repo(self):
        proc = subprocess.run(
            [sys.executable, "-m",
             "bluefog_tpu.analysis.protocol_check", "--verbose"],
            capture_output=True, text=True, timeout=300,
            cwd=REPO, env=clean_env())
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "bfwire: OK" in proc.stdout
        assert "deposit-stream:" in proc.stdout  # state counts reported


class TestFeatureDocLint:
    """BF-DOC003: the transport doc's HELLO feature-bit paragraph <->
    the live FEATURE_* constants, both directions with value
    agreement."""

    @staticmethod
    def _live_bits():
        from bluefog_tpu.runtime import window_server as ws

        return {n[len("FEATURE_"):]: v for n, v in vars(ws).items()
                if n.startswith("FEATURE_") and isinstance(v, int)}

    @staticmethod
    def _doc(tmp_path, pairs):
        doc = tmp_path / "transport.md"
        doc.write_text("HELLO feature bits: " + ", ".join(
            "%d `%s`" % (v, n) for n, v in pairs) + ".\n")
        return str(doc)

    def test_repo_feature_doc_matches_live_bits(self):
        from bluefog_tpu.analysis.doc_lint import check_feature_doc

        diags = check_feature_doc()
        assert not _errors(diags), [d.format() for d in diags]
        assert any(d.code == "BF-DOC102" for d in diags)

    def test_missing_bit_is_error(self, tmp_path):
        from bluefog_tpu.analysis.doc_lint import check_feature_doc

        live = self._live_bits()
        path = self._doc(tmp_path, [(n, v) for n, v in live.items()
                                    if n != "DELTA"])
        errs = [d for d in _errors(check_feature_doc(path))
                if d.code == "BF-DOC003"]
        assert len(errs) == 1 and "FEATURE_DELTA" in errs[0].message

    def test_wrong_value_is_error(self, tmp_path):
        from bluefog_tpu.analysis.doc_lint import check_feature_doc

        live = self._live_bits()
        path = self._doc(tmp_path,
                         [(n, 999 if n == "TRACE" else v)
                          for n, v in live.items()])
        errs = [d for d in _errors(check_feature_doc(path))
                if d.code == "BF-DOC003"]
        assert len(errs) == 1 and "999" in errs[0].message

    def test_stale_doc_entry_is_error(self, tmp_path):
        from bluefog_tpu.analysis.doc_lint import check_feature_doc

        pairs = list(self._live_bits().items()) + [("WORMHOLE", 4096)]
        path = self._doc(tmp_path, pairs)
        errs = [d for d in _errors(check_feature_doc(path))
                if d.code == "BF-DOC003"]
        assert len(errs) == 1 and "WORMHOLE" in errs[0].message

    def test_missing_paragraph_is_error(self, tmp_path):
        from bluefog_tpu.analysis.doc_lint import check_feature_doc

        doc = tmp_path / "transport.md"
        doc.write_text("no feature bit paragraph here\n")
        errs = [d for d in _errors(check_feature_doc(str(doc)))
                if d.code == "BF-DOC003"]
        assert errs and "paragraph" in errs[0].message
