"""Planet-scale read path: relay trees + op-10 delta encoding.

Covers the tentpole surfaces of ``bluefog_tpu/relay`` and the wire
machinery beneath it:

- the delta codec state machines (`runtime/delta.py`): error-feedback
  residuals, full-frame anchors, loud desync;
- the op-10 wire path end to end: delta-negotiated subscriptions keep
  the round-stamp audit exact, torn deltas never advance the cursor,
  and every cursor gap resyncs through a full-frame anchor;
- `SnapshotTable` group lifecycle: `drop_group()` + the idle-TTL sweep
  that keeps long-lived relay/fleet processes bounded;
- two-tier relay chains under the extended chaos matrix (`read:` /
  `sub:` / the new `relay:` site): a mid-tree relay killed while rounds
  roll — children resume upstream or re-parent with delivered rounds
  strictly increasing and the stamp audit exact at the leaves;
- the tree control plan (`control/tree.py`): canonical bytes, pure
  determinism, hysteresis + cooldown, the capacity arithmetic;
- the BF-RLY001 lint (re-publish without resync/cursor vocabulary) and
  the `reader_tree` sim scenario that gates staleness and delivery
  cleanliness at O(thousands) of simulated readers.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tests._util import REPO as _REPO, clean_env, uniq as _uniq


@pytest.fixture(autouse=True)
def _chaos_isolated():
    from bluefog_tpu import chaos

    chaos.reset()
    yield
    chaos.reset()


def _serve(tbl=None, delta=None):
    from bluefog_tpu.runtime.window_server import WindowServer

    srv = WindowServer(snapshots=tbl, delta=delta)
    addr = srv.start("127.0.0.1")
    return srv, addr


def _stamped(rnd: float, dim: int = 256, base=None):
    v = float(rnd)
    x = (np.full(dim, v) if base is None else np.asarray(base, float))
    return {"x": x, "p": np.array([v + 1.0]), "round": np.array([v])}


# ---------------------------------------------------------------------------
# delta codec state machines
# ---------------------------------------------------------------------------


class TestDeltaCodec:
    def test_dense_delta_roundtrip_is_exact(self):
        from bluefog_tpu.runtime.delta import (DeltaApplier, DeltaConfig,
                                               DeltaEncoder)

        cfg = DeltaConfig(full_every=100, codec="topk",
                          min_delta_elems=10_000)  # all leaves dense
        enc, app = DeltaEncoder(), DeltaApplier("g")
        rng = np.random.default_rng(0)
        x = rng.standard_normal(64)
        kind, _, _ = enc.step(0, [("x", x)], cfg)
        assert kind == 0
        app.anchor(0, {"x": x})
        for rnd in range(1, 6):
            x = x + rng.standard_normal(64)
            kind, base, items = enc.step(rnd, [("x", x)], cfg)
            assert kind == 10 and base == rnd - 1
            wire = [(n, d, c, ne,
                     memoryview(b"".join(bytes(v) for v in vs)))
                    for (n, d, c, ne, vs, _w) in items]
            leaves = app.apply(rnd, base, wire)
            np.testing.assert_allclose(leaves["x"], x, rtol=0, atol=0)

    def test_error_feedback_resyncs_exactly_at_anchors(self):
        from bluefog_tpu.runtime.delta import (DeltaApplier, DeltaConfig,
                                               DeltaEncoder)

        cfg = DeltaConfig(full_every=4, codec="topk", topk_ratio=0.1,
                          min_delta_elems=1)  # lossy for everything
        enc, app = DeltaEncoder(), DeltaApplier("g")
        rng = np.random.default_rng(1)
        x = rng.standard_normal(512)
        errs = {}
        for rnd in range(12):
            kind, base, items = enc.step(rnd, [("x", x)], cfg)
            if kind == 0:
                app.anchor(rnd, {"x": x})
            else:
                wire = [(n, d, c, ne,
                         memoryview(b"".join(bytes(v) for v in vs)))
                        for (n, d, c, ne, vs, _w) in items]
                app.apply(rnd, base, wire)
            errs[rnd] = float(np.abs(app._recon["x"] - x).max())
            x = x + 0.01 * rng.standard_normal(512)
        # anchors (push 0, 4, 8) are bit-exact; deltas are bounded-lossy
        assert errs[0] == 0.0 and errs[4] == 0.0 and errs[8] == 0.0
        assert 0 < max(errs.values()) < 0.2
        assert enc.full_frames == 3 and enc.delta_frames == 9

    def test_desync_refused_loudly(self):
        from bluefog_tpu.runtime.delta import (DeltaApplier, DeltaConfig,
                                               DeltaEncoder, DeltaDesync)
        from bluefog_tpu.runtime import wire_status

        cfg = DeltaConfig(full_every=100, min_delta_elems=10_000)
        enc, app = DeltaEncoder(), DeltaApplier("g")
        x = np.ones(8)
        enc.step(0, [("x", x)], cfg)
        app.anchor(0, {"x": x})
        _, base, items = enc.step(1, [("x", x * 2)], cfg)
        wire = [(n, d, c, ne,
                 memoryview(b"".join(bytes(v) for v in vs)))
                for (n, d, c, ne, vs, _w) in items]
        app.apply(1, base, wire)
        # replaying the same delta against the moved cursor: refused
        with pytest.raises(DeltaDesync) as ei:
            app.apply(1, base, wire)
        assert ei.value.status == wire_status.ERR_DELTA_BASE
        assert wire_status.is_retriable(ei.value.status)

    def test_geometry_change_forces_full_anchor(self):
        from bluefog_tpu.runtime.delta import DeltaConfig, DeltaEncoder

        cfg = DeltaConfig(full_every=100, min_delta_elems=10_000)
        enc = DeltaEncoder()
        assert enc.step(0, [("x", np.ones(8))], cfg)[0] == 0
        assert enc.step(1, [("x", np.ones(8))], cfg)[0] == 10
        # a new leaf set cannot diff against the old base: full frame
        assert enc.step(2, [("x", np.ones(8)),
                            ("y", np.ones(4))], cfg)[0] == 0
        # so does a reshaped leaf
        assert enc.step(3, [("x", np.ones(16)),
                            ("y", np.ones(4))], cfg)[0] == 0

    def test_config_validation(self):
        from bluefog_tpu.runtime.delta import DeltaConfig

        with pytest.raises(ValueError, match="full_every"):
            DeltaConfig(full_every=0)
        with pytest.raises(ValueError, match="codec"):
            DeltaConfig(codec="zstd")
        with pytest.raises(ValueError, match="topk_ratio"):
            DeltaConfig(topk_ratio=0.0)


# ---------------------------------------------------------------------------
# op-10 wire path
# ---------------------------------------------------------------------------


class TestDeltaWire:
    def test_delta_subscription_stays_round_exact(self):
        from bluefog_tpu.runtime.delta import DeltaConfig
        from bluefog_tpu.serving.snapshots import SnapshotTable
        from bluefog_tpu.serving.subscriber import Subscriber

        tbl = SnapshotTable()
        srv, addr = _serve(tbl, DeltaConfig(full_every=4, codec="topk",
                                            min_delta_elems=64))
        g = _uniq("dwire")
        rng = np.random.default_rng(2)
        x = rng.standard_normal(1024)
        tbl.publish(g, 0, _stamped(0, base=x))
        got = []
        sub = Subscriber(addr, g, delta=True,
                         on_snapshot=lambda s: got.append(s))
        try:
            for rnd in range(1, 12):
                x = x + 0.01 * rng.standard_normal(1024)
                tbl.publish(g, rnd, _stamped(rnd, base=x))
                time.sleep(0.03)
            deadline = time.monotonic() + 10
            while (not got or got[-1].round < 11) \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            rounds = [s.round for s in got]
            assert rounds and rounds[-1] == 11
            assert rounds == sorted(set(rounds))
            assert sub.delta_frames > 0, "deltas never engaged"
            for s in got:
                # the exactness floor: the round stamp and p mass ride
                # densely inside delta frames, bit-exact at every hop
                assert float(s["round"][0]) == s.round
                assert float(s["p"][0]) == s.round + 1.0
        finally:
            sub.close()
            srv.stop()

    def test_torn_delta_never_advances_cursor_and_resyncs(self):
        from bluefog_tpu import chaos
        from bluefog_tpu.runtime.delta import DeltaConfig
        from bluefog_tpu.serving.snapshots import SnapshotTable
        from bluefog_tpu.serving.subscriber import Subscriber

        tbl = SnapshotTable()
        srv, addr = _serve(tbl, DeltaConfig(full_every=100,
                                            min_delta_elems=64))
        g = _uniq("dtorn")
        rng = np.random.default_rng(3)
        x = rng.standard_normal(2048)
        tbl.publish(g, 0, _stamped(0, base=x))
        got = []
        # tear the push channel mid-frame on the 4th push: with
        # full_every=100 the torn frame is a DELTA — the cursor must
        # not move, and the resumed stream resyncs via a full anchor
        chaos.configure("sub:truncate:after_frames=4")
        sub = Subscriber(addr, g, delta=True,
                         reconnect=dict(base_s=0.05, budget=8, seed=0),
                         on_snapshot=lambda s: got.append(s))
        try:
            for rnd in range(1, 14):
                x = x + 0.01 * rng.standard_normal(2048)
                tbl.publish(g, rnd, _stamped(rnd, base=x))
                time.sleep(0.05)
            deadline = time.monotonic() + 15
            while (not got or got[-1].round < 13) \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            rounds = [s.round for s in got]
            assert rounds[-1] == 13, rounds
            assert rounds == sorted(set(rounds)), \
                f"duplicate/regressed delivery: {rounds}"
            assert sub.resumes >= 1, "the cut never resumed"
            for s in got:
                assert float(s["round"][0]) == s.round
        finally:
            sub.close()
            srv.stop()

    def test_plain_subscriber_unaffected_by_delta_server(self):
        from bluefog_tpu.runtime.delta import DeltaConfig
        from bluefog_tpu.serving.snapshots import SnapshotTable
        from bluefog_tpu.serving.subscriber import Subscriber

        tbl = SnapshotTable()
        srv, addr = _serve(tbl, DeltaConfig(full_every=2))
        g = _uniq("dplain")
        tbl.publish(g, 3, _stamped(3))
        got = []
        sub = Subscriber(addr, g, on_snapshot=lambda s: got.append(s))
        try:
            deadline = time.monotonic() + 10
            while not got and time.monotonic() < deadline:
                time.sleep(0.01)
            assert got and got[0].round == 3
            assert (got[0]["x"] == 3.0).all()
            assert sub.delta_frames == 0
        finally:
            sub.close()
            srv.stop()

    def test_fanout_limit_refuses_retriably(self):
        from bluefog_tpu.runtime import wire_status
        from bluefog_tpu.serving.snapshots import SnapshotTable
        from bluefog_tpu.serving.subscriber import Subscriber

        tbl = SnapshotTable()
        srv, addr = _serve(tbl)
        srv.set_fanout_limit(1)
        g = _uniq("fanout")
        tbl.publish(g, 1, _stamped(1))
        first = Subscriber(addr, g)
        got = []
        try:
            deadline = time.monotonic() + 10
            while first.cursor < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            # the second subscription is over the degree limit: the
            # refusal is ERR_BUSY (retriable) — with reconnect off it
            # latches as an error naming the busy status, never a crash
            second = Subscriber(addr, g, reconnect=False,
                                on_snapshot=lambda s: got.append(s))
            deadline = time.monotonic() + 10
            while second.error is None and time.monotonic() < deadline:
                time.sleep(0.01)
            assert second.error is not None
            assert not got
            second.close()
            # a freed slot admits the next reader
            first.close()
            third = Subscriber(addr, g)
            deadline = time.monotonic() + 10
            while third.cursor < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert third.cursor == 1
            third.close()
            assert wire_status.is_retriable(wire_status.ERR_BUSY)
        finally:
            first.close()
            srv.stop()


# ---------------------------------------------------------------------------
# SnapshotTable group lifecycle (long-lived processes)
# ---------------------------------------------------------------------------


class TestGroupLifecycle:
    def test_drop_group_reports_existence(self):
        from bluefog_tpu.serving.snapshots import SnapshotTable

        tbl = SnapshotTable()
        g = _uniq("lcg")
        tbl.publish(g, 0, _stamped(0))
        assert g in tbl.groups()
        assert tbl.drop_group(g) is True
        assert tbl.drop_group(g) is False
        assert g not in tbl.groups()

    def test_idle_ttl_sweep_drops_only_idle_groups(self):
        from bluefog_tpu.serving.snapshots import SnapshotTable

        tbl = SnapshotTable()
        fresh, stale = _uniq("fresh"), _uniq("stale")
        tbl.publish(stale, 0, _stamped(0))
        t_mid = time.monotonic() + 100.0
        tbl.publish(fresh, 0, _stamped(0))
        # pin the fresh group's publish time after the virtual "now"
        # minus ttl: sweep at now=+100 with ttl 50 drops only `stale`
        with tbl._mu:
            tbl._groups[fresh].published_at = t_mid - 1.0
        dropped = tbl.sweep_idle(50.0, now=t_mid)
        assert dropped == [stale]
        assert tbl.groups() == [fresh]
        # nothing left to drop on a re-sweep
        assert tbl.sweep_idle(50.0, now=t_mid) == []

    def test_sweep_ages_never_published_groups_from_creation(self):
        from bluefog_tpu.serving.snapshots import SnapshotTable

        tbl = SnapshotTable()
        g = _uniq("neverpub")
        tbl._group(g)  # created (a subscriber waiting), never published
        assert tbl.sweep_idle(3600.0) == []
        dropped = tbl.sweep_idle(
            0.001, now=time.monotonic() + 10.0)
        assert g in dropped

    def test_wait_newer_wakes_on_generation_regression(self):
        """A swept-and-revived group restarts its generation counter:
        a sender parked on the OLD high generation must wake on the
        revived group's first publish, not starve until the new counter
        catches up (the sweep-starvation regression)."""
        from bluefog_tpu.serving.snapshots import SnapshotTable

        tbl = SnapshotTable()
        g = _uniq("regen")
        for rnd in range(50):
            tbl.publish(g, rnd, _stamped(rnd))
        high = tbl.generation(g)
        assert high == 50
        assert tbl.sweep_idle(1.0, now=time.monotonic() + 100) == [g]
        tbl.publish(g, 50, _stamped(50))
        # the revived group's gen (1) sits BELOW the parked gen (50):
        # wait_newer must return immediately, not time out
        assert tbl.wait_newer(g, high, timeout_s=2.0) == 1
        assert tbl.read(g)[0] == 50

    def test_subscriber_survives_sweep_and_revive(self):
        from bluefog_tpu.serving.snapshots import SnapshotTable
        from bluefog_tpu.serving.subscriber import Subscriber

        tbl = SnapshotTable()
        srv, addr = _serve(tbl)
        g = _uniq("revive")
        for rnd in range(20):
            tbl.publish(g, rnd, _stamped(rnd))
        got = []
        sub = Subscriber(addr, g, on_snapshot=lambda s: got.append(s))
        try:
            deadline = time.monotonic() + 10
            while sub.cursor < 19 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert sub.cursor == 19
            tbl.sweep_idle(1.0, now=time.monotonic() + 100)
            tbl.publish(g, 20, _stamped(20))
            deadline = time.monotonic() + 10
            while sub.cursor < 20 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert sub.cursor == 20, "sender starved after sweep+revive"
            rounds = [s.round for s in got]
            assert rounds == sorted(set(rounds))
        finally:
            sub.close()
            srv.stop()

    def test_fanout_reservation_is_atomic(self):
        """N concurrent claims against one free slot: exactly one wins
        (the re-parent-storm case the check-and-increment exists for)."""
        from bluefog_tpu.serving.snapshots import SnapshotTable

        tbl = SnapshotTable()
        srv, addr = _serve(tbl)
        srv.set_fanout_limit(1)
        inner = srv._server
        wins = []
        start = threading.Barrier(8)

        def claim():
            start.wait()
            if inner.sub_reserve():
                wins.append(1)

        threads = [threading.Thread(target=claim) for _ in range(8)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
            assert sum(wins) == 1
            inner.note_sub(-1)
            assert inner.sub_reserve()  # the released slot re-admits
        finally:
            srv.stop()

    def test_group_gauge_tracks_census(self):
        from bluefog_tpu.metrics.registry import metrics_start, metrics_stop
        from bluefog_tpu.serving.snapshots import SnapshotTable

        reg = metrics_start()
        try:
            tbl = SnapshotTable()
            a, b = _uniq("ga"), _uniq("gb")
            tbl.publish(a, 0, _stamped(0))
            tbl.publish(b, 0, _stamped(0))
            snap = reg.snapshot()
            assert snap.get("bf_snapshot_groups") == 2.0
            tbl.drop_group(a)
            assert reg.snapshot().get("bf_snapshot_groups") == 1.0
        finally:
            metrics_stop()


# ---------------------------------------------------------------------------
# two-tier relay chains (the PR 7 torn-read/chaos matrix, extended)
# ---------------------------------------------------------------------------


def _publish_rounds(tbl, g, x, rng, start, stop_, dt=0.04):
    for rnd in range(start, stop_):
        np.add(x, 0.01 * rng.standard_normal(x.size), out=x)
        tbl.publish(g, rnd, {"x": x, "p": np.array([float(rnd + 1)]),
                             "round": np.array([float(rnd)])})
        time.sleep(dt)


class TestRelayChain:
    def _chain(self, tbl, addr, g, **t2_kw):
        from bluefog_tpu.relay.node import RelayNode
        from bluefog_tpu.runtime.delta import DeltaConfig

        dc = DeltaConfig(full_every=4, min_delta_elems=64)
        t1 = RelayNode(addr, [g], tier=1, delta=dc)
        t2 = RelayNode(t1.address, [g], tier=2, delta=dc, **t2_kw)
        return t1, t2

    def test_two_tier_chain_exact_stamps_strictly_increasing(self):
        from bluefog_tpu.serving.snapshots import SnapshotTable
        from bluefog_tpu.serving.subscriber import Subscriber

        tbl = SnapshotTable()
        srv, addr = _serve(tbl)
        g = _uniq("chain")
        rng = np.random.default_rng(4)
        x = rng.standard_normal(512)
        tbl.publish(g, 0, {"x": x, "p": np.array([1.0]),
                           "round": np.array([0.0])})
        t1 = t2 = leaf = None
        try:
            t1, t2 = self._chain(tbl, addr, g)
            got = []
            leaf = Subscriber(t2.address, g, delta=True,
                              on_snapshot=lambda s: got.append(s))
            _publish_rounds(tbl, g, x, rng, 1, 16)
            deadline = time.monotonic() + 15
            while (not got or got[-1].round < 15) \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            rounds = [s.round for s in got]
            assert rounds and rounds[-1] == 15
            assert rounds == sorted(set(rounds))
            for s in got:  # the leaf-level exact stamp audit
                assert float(s["round"][0]) == s.round
                assert float(s["p"][0]) == s.round + 1.0
            assert t1.landed > 0 and t2.landed > 0
        finally:
            for closer in (leaf, t2, t1):
                if closer is not None:
                    closer.close()
            srv.stop()

    @pytest.mark.chaos
    def test_mid_tree_kill_children_reparent_nothing_lost(self):
        from bluefog_tpu.serving.snapshots import SnapshotTable
        from bluefog_tpu.serving.subscriber import Subscriber

        tbl = SnapshotTable()
        srv, addr = _serve(tbl)
        g = _uniq("kill")
        rng = np.random.default_rng(5)
        x = rng.standard_normal(512)
        tbl.publish(g, 0, {"x": x, "p": np.array([1.0]),
                           "round": np.array([0.0])})
        t1 = t2 = leaf = None
        try:
            t1, t2 = self._chain(
                tbl, addr, g, fallbacks=[addr],
                reconnect=dict(base_s=0.05, budget=3, seed=0))
            got = []
            leaf = Subscriber(t2.address, g, delta=True,
                              on_snapshot=lambda s: got.append(s))
            _publish_rounds(tbl, g, x, rng, 1, 10)
            # kill the mid-tree relay: t2 must exhaust its uplink
            # budget, RE-PARENT to the root (cursor preserved), and the
            # leaf's delivered rounds stay strictly increasing
            t1.close()
            t1 = None
            _publish_rounds(tbl, g, x, rng, 10, 26, dt=0.06)
            deadline = time.monotonic() + 30
            while (not got or got[-1].round < 25) \
                    and time.monotonic() < deadline:
                time.sleep(0.05)
            rounds = [s.round for s in got]
            assert rounds and rounds[-1] == 25, rounds[-5:]
            assert rounds == sorted(set(rounds)), \
                f"dup/regressed after re-parent: {rounds}"
            assert t2.reparents >= 1
            for s in got:
                assert float(s["round"][0]) == s.round
        finally:
            for closer in (leaf, t2, t1):
                if closer is not None:
                    closer.close()
            srv.stop()

    @pytest.mark.chaos
    def test_chaos_matrix_on_two_tier_chain(self):
        """`read:`/`sub:`/`relay:` faults against the whole tree: torn
        pushes, stalled re-publishes, dropped relay lands — delivered
        rounds stay strictly increasing with exact stamps at the
        leaf, and the relay records its chaos drops as skips."""
        from bluefog_tpu import chaos
        from bluefog_tpu.serving.snapshots import SnapshotTable
        from bluefog_tpu.serving.subscriber import Subscriber

        tbl = SnapshotTable()
        srv, addr = _serve(tbl)
        g = _uniq("cmx")
        rng = np.random.default_rng(6)
        x = rng.standard_normal(512)
        tbl.publish(g, 0, {"x": x, "p": np.array([1.0]),
                           "round": np.array([0.0])})
        chaos.configure("sub:truncate:every=9;relay:drop:every=7;"
                        "relay:delay:ms=20:every=5;read:stall:s=0.1:every=11")
        t1 = t2 = leaf = None
        try:
            t1, t2 = self._chain(
                tbl, addr, g, fallbacks=[addr],
                reconnect=dict(base_s=0.05, budget=6, seed=0))
            got = []
            leaf = Subscriber(t2.address, g, delta=True,
                              reconnect=dict(base_s=0.05, budget=8,
                                             seed=1),
                              on_snapshot=lambda s: got.append(s))
            _publish_rounds(tbl, g, x, rng, 1, 30, dt=0.05)
            deadline = time.monotonic() + 30
            while (not got or got[-1].round < 27) \
                    and time.monotonic() < deadline:
                time.sleep(0.05)
            rounds = [s.round for s in got]
            assert rounds, "nothing delivered under chaos"
            assert rounds[-1] >= 27, rounds[-5:]
            assert rounds == sorted(set(rounds)), rounds
            for s in got:
                assert float(s["round"][0]) == s.round
                assert float(s["p"][0]) == s.round + 1.0
        finally:
            for closer in (leaf, t2, t1):
                if closer is not None:
                    closer.close()
            srv.stop()

    def test_relay_refuses_self_loop(self):
        import socket

        from bluefog_tpu.relay.node import RelayNode
        from bluefog_tpu.runtime import wire_status

        # a relay configured with ITS OWN serving address as upstream
        # (a mis-wired tree closing a cycle): refused loudly with the
        # registry's -110 before any wire traffic
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        with pytest.raises(RuntimeError,
                           match=str(wire_status.ERR_RELAY_LOOP)):
            RelayNode(("127.0.0.1", port), ["g"], tier=1,
                      host="127.0.0.1", port=port)

    def test_relay_sweeps_idle_groups(self):
        from bluefog_tpu.relay.node import RelayNode
        from bluefog_tpu.serving.snapshots import SnapshotTable

        tbl = SnapshotTable()
        srv, addr = _serve(tbl)
        g = _uniq("sweep")
        tbl.publish(g, 1, _stamped(1))
        node = None
        try:
            node = RelayNode(addr, [g], tier=1, idle_ttl_s=0.4)
            node.wait_ready(timeout_s=15)
            deadline = time.monotonic() + 10
            while g in node.table.groups() \
                    and time.monotonic() < deadline:
                time.sleep(0.1)
            # nothing published upstream for > ttl: the relay's sweep
            # evicted the idle group (the next land re-creates it)
            assert g not in node.table.groups()
        finally:
            if node is not None:
                node.close()
            srv.stop()


def test_bfrelay_cli_runs_and_serves():
    """The standalone relay process: RELAY_READY line, serves the
    group, exits 0 at --duration."""
    from bluefog_tpu.serving.snapshots import SnapshotTable
    from bluefog_tpu.serving.client import SnapshotClient

    tbl = SnapshotTable()
    srv, addr = _serve(tbl)
    g = _uniq("cli")
    tbl.publish(g, 7, _stamped(7))
    proc = subprocess.Popen(
        [sys.executable, "-m", "bluefog_tpu.relay",
         f"{addr[0]}:{addr[1]}", "--group", g, "--host", "127.0.0.1",
         "--duration", "6", "--degree", "4"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=clean_env(), cwd=_REPO)
    try:
        line = proc.stdout.readline().strip().split()
        assert line[:1] == ["RELAY_READY"], line
        raddr = (line[1], int(line[2]))
        with SnapshotClient(raddr, g) as c:
            snap = c.snapshot(min_round=7, wait_s=10.0)
            assert snap.round == 7 and float(snap["round"][0]) == 7.0
        out, _ = proc.communicate(timeout=30)
        assert proc.returncode == 0, out
    finally:
        if proc.poll() is None:
            proc.kill()
        srv.stop()
        tbl.drop(g)


# ---------------------------------------------------------------------------
# tree control plan
# ---------------------------------------------------------------------------


class TestTreePlan:
    def test_canonical_bytes_roundtrip(self):
        from bluefog_tpu.control.tree import TreePlan

        p = TreePlan(version=3, round=40, degree=8, depth=2,
                     full_every=16)
        assert TreePlan.from_bytes(p.to_bytes()) == p
        assert p.to_bytes() == TreePlan.from_bytes(p.to_bytes()).to_bytes()

    def test_field_normalization_and_capacity(self):
        from bluefog_tpu.control.tree import TreePlan, tree_capacity

        p = TreePlan(degree=0, depth=-1, full_every=0)
        assert p.degree == 2 and p.depth == 0 and p.full_every == 1
        assert tree_capacity(8, 2) == 512
        assert tree_capacity(2, 0) == 2

    def test_decide_is_pure_and_order_independent(self):
        from bluefog_tpu.control.tree import (TreeConfig, TreeEvidence,
                                              TreePlan, decide_tree_plan)

        evs = [TreeEvidence("n0", tier=0, subscribers=60,
                            skip_rate=0.01, staleness_rounds=0.5),
               TreeEvidence("n1", tier=1, subscribers=8,
                            skip_rate=0.4, staleness_rounds=6.0)]
        cfg = TreeConfig()
        a = decide_tree_plan(TreePlan(), 10, evs, cfg)
        b = decide_tree_plan(TreePlan(), 10, list(reversed(evs)), cfg)
        assert a.to_bytes() == b.to_bytes()
        assert a.version == 1

    def test_decision_table(self):
        from bluefog_tpu.control.tree import (TreeConfig, TreeEvidence,
                                              TreePlan, decide_tree_plan)

        cfg = TreeConfig(degree_max=8, full_every_max=32)
        # overload: high skip halves degree, staleness halves the
        # anchor cadence, demand over capacity deepens the tree
        prev = TreePlan(version=1, round=0, degree=8, depth=1,
                        full_every=8)
        evs = [TreeEvidence("n0", subscribers=100, skip_rate=0.5,
                            staleness_rounds=10.0)]
        plan = decide_tree_plan(prev, 100, evs, cfg)
        assert plan.degree == 4
        assert plan.full_every == 4
        assert plan.depth == 2  # 100 > 0.9 * 4^2
        # comfort: everything re-arms toward the ceilings
        calm = [TreeEvidence("n0", subscribers=3, skip_rate=0.0,
                             staleness_rounds=0.1)]
        plan2 = decide_tree_plan(plan, 200, calm, cfg)
        assert plan2.degree == 8
        assert plan2.full_every == 8
        assert plan2.depth == 1
        # no evidence, no change — same object
        assert decide_tree_plan(plan2, 300, [], cfg) is plan2

    def test_cooldown_and_no_flap(self):
        from bluefog_tpu.control.tree import (TreeConfig, TreeEvidence,
                                              TreePlan, decide_tree_plan)

        cfg = TreeConfig(cooldown_rounds=16)
        evs = [TreeEvidence("n0", subscribers=4, skip_rate=0.5,
                            staleness_rounds=0.2)]
        p1 = decide_tree_plan(TreePlan(), 10, evs, cfg)
        assert p1.version == 1
        # inside the cooldown: immune, same object
        assert decide_tree_plan(p1, 20, evs, cfg) is p1
        # the hysteresis band's middle ground changes nothing
        mid = [TreeEvidence("n0", subscribers=4, skip_rate=0.1,
                            staleness_rounds=2.0)]
        assert decide_tree_plan(p1, 40, mid, cfg) is p1

    def test_config_hysteresis_validation(self):
        from bluefog_tpu.control.tree import TreeConfig

        with pytest.raises(ValueError, match="skip_exit"):
            TreeConfig(skip_enter=0.01, skip_exit=0.05)
        with pytest.raises(ValueError, match="stale_exit"):
            TreeConfig(stale_enter=1.0, stale_exit=2.0)
        with pytest.raises(ValueError, match="fan_exit"):
            TreeConfig(fan_enter=0.1, fan_exit=0.2)

    def test_relay_actuates_plan_at_boundary(self):
        """apply_plan swaps delta cadence + fan-out limit between
        rounds (this test IS the round-boundary/quiesce context the
        BF-CTL001 discipline requires: nothing in flight here)."""
        from bluefog_tpu.control.tree import TreePlan
        from bluefog_tpu.relay.node import RelayNode
        from bluefog_tpu.serving.snapshots import SnapshotTable

        tbl = SnapshotTable()
        srv, addr = _serve(tbl)
        g = _uniq("actuate")
        tbl.publish(g, 1, _stamped(1))
        node = None
        try:
            node = RelayNode(addr, [g], tier=1)
            node.wait_ready(timeout_s=15)
            # the round boundary: the relay's table is quiesced between
            # landed rounds while nothing is being published upstream
            node.apply_plan(TreePlan(version=2, round=1, degree=3,
                                     depth=1, full_every=2))
            assert node.server._server.sub_limit == 3
            assert node.server._server.delta_cfg.full_every == 2
        finally:
            if node is not None:
                node.close()
            srv.stop()


# ---------------------------------------------------------------------------
# BF-RLY001 lint
# ---------------------------------------------------------------------------


class TestRelayLint:
    def test_guard_free_republish_flagged(self):
        from bluefog_tpu.analysis.relay_lint import find_republish_sites

        bad = (
            "import bluefog_tpu.relay\n"
            "def forward(tbl, snap):\n"
            "    tbl.publish('g', snap.round, snap.leaves)\n")
        diags = find_republish_sites(bad, filename="bad.py")
        assert any(d.code == "BF-RLY001" and d.severity == "error"
                   for d in diags)

    def test_cursor_guard_passes(self):
        from bluefog_tpu.analysis.relay_lint import find_republish_sites

        ok = (
            "import bluefog_tpu.relay\n"
            "def forward(tbl, snap):\n"
            "    cursor = tbl.current_round('g')\n"
            "    if snap.round <= cursor:\n"
            "        return\n"
            "    tbl.publish('g', snap.round, snap.leaves)\n")
        assert find_republish_sites(ok, filename="ok.py") == []

    def test_desync_handler_passes(self):
        from bluefog_tpu.analysis.relay_lint import find_republish_sites

        ok = (
            "from bluefog_tpu.relay import RelayNode\n"
            "from bluefog_tpu.runtime.delta import DeltaDesync\n"
            "def forward(tbl, snap):\n"
            "    try:\n"
            "        tbl.publish('g', snap.round, snap.leaves)\n"
            "    except DeltaDesync:\n"
            "        pass\n")
        assert find_republish_sites(ok, filename="ok2.py") == []

    def test_plain_publisher_out_of_scope(self):
        from bluefog_tpu.analysis.relay_lint import find_republish_sites

        ok = (
            "import bluefog_tpu.relay\n"
            "import numpy as np\n"
            "def publish_model(tbl, rnd, x):\n"
            "    tbl.publish('g', rnd, {'x': x})\n")
        assert find_republish_sites(ok, filename="pub.py") == []

    def test_non_relay_module_out_of_scope(self):
        from bluefog_tpu.analysis.relay_lint import find_republish_sites

        src = (
            "def forward(tbl, snap):\n"
            "    tbl.publish('g', snap.round, snap.leaves)\n")
        assert find_republish_sites(src, filename="other.py") == []

    def test_relay_node_itself_is_clean(self):
        from bluefog_tpu.analysis.relay_lint import check_file

        path = os.path.join(_REPO, "bluefog_tpu", "relay", "node.py")
        assert [d for d in check_file(path)
                if d.severity == "error"] == []


# ---------------------------------------------------------------------------
# reader_tree sim scenario
# ---------------------------------------------------------------------------


class TestReaderTreeSim:
    def test_thousands_of_readers_clean_and_bounded(self):
        from bluefog_tpu.sim.readers import (ReaderTreeConfig,
                                             run_reader_tree)

        rep = run_reader_tree(ReaderTreeConfig(
            readers=2000, degree=16, depth=2, rounds=60,
            publish_dt_s=0.01, hop_dt_s=0.009, seed=3,
            kill=((0.25, 1, 0),)))
        assert rep.readers == 2000
        assert rep.duplicates == 0 and rep.regressions == 0 \
            and rep.torn == 0
        assert rep.readers_served == 2000
        assert rep.min_reader_final_round >= 53  # 0.9 * 59
        # staleness adds per tier, bounded
        for tier, worst in rep.worst_staleness_by_tier.items():
            assert worst <= 3 * max(1, tier), (tier, worst)

    def test_deterministic_same_seed_same_report(self):
        from bluefog_tpu.sim.readers import (ReaderTreeConfig,
                                             run_reader_tree)

        cfg = ReaderTreeConfig(readers=300, degree=8, depth=2,
                               rounds=40, seed=7, kill=((0.2, 1, 1),))
        a = run_reader_tree(cfg).as_dict()
        b = run_reader_tree(cfg).as_dict()
        assert a == b

    def test_over_capacity_config_refused(self):
        from bluefog_tpu.sim.readers import ReaderTreeConfig

        # 2000 readers cannot ride a degree-8 depth-2 tree (capacity
        # 512) at honest per-node degree: refused, never quietly
        # simulated with over-degree leaf fan-out
        with pytest.raises(ValueError, match="capacity"):
            ReaderTreeConfig(readers=2000, degree=8, depth=2)

    def test_every_tier_respects_degree(self):
        from bluefog_tpu.sim.readers import (ReaderTreeConfig,
                                             run_reader_tree)

        rep = run_reader_tree(ReaderTreeConfig(
            readers=2000, degree=16, depth=2, rounds=5))
        # leaf tier ceil(2000/16)=125 nodes, tier 1 ceil(125/16)=8:
        # every node's children (relays AND readers) fit the degree
        assert rep.relays == 125 + 8

    def test_scenario_rides_the_suite(self):
        from bluefog_tpu.sim.scenarios import (SCENARIO_NAMES,
                                               build_suite, run_scenario,
                                               reader_tree)

        assert "reader_tree" in SCENARIO_NAMES
        sc = next(s for s in build_suite(n=48)
                  if s.name == "reader_tree")
        assert sc.kind == "reader_tree" and sc.accept
        rep = run_scenario(reader_tree(n=48, seed=0))
        assert rep["ok"], rep["predicates"]
        assert rep["reader_tree"]["duplicates"] == 0

    def test_chaos_relay_site_parses_and_sim_refuses_it(self):
        """The grammar knows `relay:`; the deposit-path fleet sim
        refuses it as inert (the reader-tree model is where relay
        faults live)."""
        from bluefog_tpu.chaos import parse_spec
        from bluefog_tpu.sim.network import LinkModel

        rules = parse_spec("relay:drop:every=9;relay:truncate:every=4")
        assert [r.site for r in rules] == ["relay", "relay"]
        lm = LinkModel(seed=0)
        with pytest.raises(ValueError, match="relay"):
            lm.set_host_faults(0, "relay:drop:every=3")
