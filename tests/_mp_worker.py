"""Multi-process test worker (launched by test_multiprocess.py, one OS
process per rank — the analog of the reference's ``mpirun -np N pytest``
harness, SURVEY.md §4).

argv: <process_id> <num_processes> <coordinator_port>

Each process owns 2 virtual CPU devices; the global mesh spans
``2 * num_processes`` devices across real process boundaries, with gloo
carrying the cross-process collectives.  Asserts, printing MP_WORKER_OK on
success:

1. loud rendezvous via ``initialize_cluster`` (explicit args);
2. ``process_rank``/``process_count`` and a spanning ``bf.init`` context;
3. closed-form gossip (neighbor_allreduce) ACROSS the process boundary;
4. closed-form global allreduce;
5. hierarchical gossip with the process boundary as the machine boundary,
   in BOTH forms — flat mesh and the two-level (machine, local) mesh whose
   outer axis crosses processes (the multi-slice/DCN shape);
6. ``win_mutex`` is a real cross-process lock: racing read-modify-write
   increments on the coordination-service KV never lose an update;
7. ``win_mutex_break`` recovers a stale lock whose owner died (timeout
   names the dead owner; after break the mutex is acquirable again) —
   the manual path, still needed for lease-less keys;
8. a LEASED lock whose owner died auto-recovers with no manual break
   (the lease expired, the next contender steals through the break
   subkey), while a LIVE slow holder is never stolen (its heartbeat
   refreshes the lease faster than it expires);
9. ``win_mutex_sweep`` clears exactly the expired-lease keys (the
   supervisor-restart janitor).
"""

import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")

import numpy as np

LOCAL_DEVICES = 2
MUTEX_ITERS = 15


def main():
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

    from bluefog_tpu.runtime.launch import initialize_cluster

    initialize_cluster(f"127.0.0.1:{port}", nproc, pid,
                       initialization_timeout=60)

    import bluefog_tpu as bf
    from bluefog_tpu.ops import collectives as C
    from bluefog_tpu.parallel.api import shard_map, win_mutex
    from bluefog_tpu.topology import RingGraph
    from bluefog_tpu.topology.schedule import build_schedule

    assert jax.process_count() == nproc, jax.process_count()
    assert bf.process_rank() == pid
    n = nproc * LOCAL_DEVICES
    assert len(jax.devices()) == n

    ctx = bf.init(topology=RingGraph(n))
    assert ctx.size == n
    # rank(): mesh-rank of this controller's first device
    assert bf.rank() == pid * LOCAL_DEVICES, bf.rank()

    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as P

    sched = build_schedule(RingGraph(n))
    xs_global = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    local = xs_global[pid * LOCAL_DEVICES:(pid + 1) * LOCAL_DEVICES]
    xs = multihost_utils.host_local_array_to_global_array(
        local, ctx.mesh, P(ctx.axis_name))

    # 3. gossip across the process boundary, closed form: out = W @ xs
    f = jax.jit(shard_map(
        lambda v: C.neighbor_allreduce(v, sched, ctx.axis_name),
        mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
        out_specs=P(ctx.axis_name), check_vma=False))
    out = f(xs)
    want = RingGraph(n).weights @ xs_global
    for shard in out.addressable_shards:
        row = shard.index[0].start  # global row of this local shard
        np.testing.assert_allclose(
            np.asarray(shard.data), want[row:row + 1], rtol=1e-6, atol=1e-6)

    # 4. global allreduce (mean) across both processes
    g = jax.jit(shard_map(
        lambda v: C.allreduce(v, ctx.axis_name, average=True),
        mesh=ctx.mesh, in_specs=(P(ctx.axis_name),), out_specs=P(ctx.axis_name),
        check_vma=False))
    mean_out = g(xs)
    for shard in mean_out.addressable_shards:
        np.testing.assert_allclose(
            np.asarray(shard.data)[0], xs_global.mean(axis=0), rtol=1e-6)

    # 5. hierarchical gossip with the PROCESS boundary as the machine
    # boundary — both forms: flat mesh (axis_index_groups) and the
    # two-level (machine, local) mesh whose outer axis crosses processes
    # (the multi-slice/DCN shape).  Closed form: machine means, then W @ m.
    ctx2 = bf.init(topology=RingGraph(n), local_size=LOCAL_DEVICES,
                   machine_topology=RingGraph(nproc), use_ici_order=False)
    assert bf.machine_rank() == pid and bf.local_rank() == 0
    msched = ctx2.machine_schedule
    means = xs_global.reshape(nproc, LOCAL_DEVICES, -1).mean(axis=1)
    want_h = (RingGraph(nproc).weights @ means)

    flat_fn = jax.jit(shard_map(
        lambda v: C.hierarchical_neighbor_allreduce(
            v, msched, ctx2.axis_name, local_size=LOCAL_DEVICES),
        mesh=ctx2.mesh, in_specs=(P(ctx2.axis_name),),
        out_specs=P(ctx2.axis_name), check_vma=False))
    xs2 = multihost_utils.host_local_array_to_global_array(
        local, ctx2.mesh, P(ctx2.axis_name))
    for shard in flat_fn(xs2).addressable_shards:
        row = shard.index[0].start
        np.testing.assert_allclose(
            np.asarray(shard.data)[0], want_h[row // LOCAL_DEVICES],
            rtol=1e-6, atol=1e-6)

    spec2 = P((ctx2.machine_axis_name, ctx2.local_axis_name))
    two_fn = jax.jit(shard_map(
        lambda v: C.hierarchical_neighbor_allreduce_2d(
            v, msched, machine_axis=ctx2.machine_axis_name,
            local_axis=ctx2.local_axis_name),
        mesh=ctx2.hier_mesh, in_specs=(spec2,), out_specs=spec2,
        check_vma=False))
    xs3 = multihost_utils.host_local_array_to_global_array(
        local, ctx2.hier_mesh, spec2)
    for shard in two_fn(xs3).addressable_shards:
        row = shard.index[0].start
        np.testing.assert_allclose(
            np.asarray(shard.data)[0], want_h[row // LOCAL_DEVICES],
            rtol=1e-6, atol=1e-6)

    # 6. win_mutex: cross-process read-modify-write must not lose updates
    from jax._src.distributed import global_state
    client = global_state.client
    if pid == 0:
        client.key_value_set("mp_counter", "0")
    client.wait_at_barrier("mutex_start", 30_000)
    for _ in range(MUTEX_ITERS):
        with win_mutex("mp_test"):
            v = int(client.blocking_key_value_get("mp_counter", 10_000))
            time.sleep(0.002)  # widen the race window
            client.key_value_set("mp_counter", str(v + 1),
                                 allow_overwrite=True)
    client.wait_at_barrier("mutex_end", 60_000)
    total = int(client.blocking_key_value_get("mp_counter", 10_000))
    assert total == nproc * MUTEX_ITERS, (
        f"lost updates: counter {total} != {nproc * MUTEX_ITERS}")

    # 7. win_mutex_break: a dead owner's stale lock blocks acquisition
    # (TimeoutError naming the owner), break clears it, and the mutex is
    # acquirable again — the MPI_Win_unlock_all-after-failure analog.
    from bluefog_tpu.parallel.api import win_mutex_break

    if pid == 0:
        client.key_value_set("bluefog_tpu/win_mutex/stale_probe",
                             "999:1:1")  # an owner that no longer exists
    client.wait_at_barrier("break_start", 30_000)
    if pid == 1:
        try:
            with win_mutex("stale_probe", timeout_s=0.5):
                raise AssertionError("acquired a lock a dead owner holds")
        except TimeoutError as e:
            assert "999:1:1" in str(e), e  # names the dead owner
        assert win_mutex_break("stale_probe") is True
        with win_mutex("stale_probe", timeout_s=5):
            pass  # recovered
    client.wait_at_barrier("break_end", 60_000)

    # 8a. expired lease -> automatic recovery, no manual break anywhere.
    # A dead leased holder leaves exactly this state behind: a value with
    # a lease stamp in the past and no heartbeat refreshing it.
    from bluefog_tpu.parallel.api import (_LEASE_MARK, _WIN_MUTEX_PREFIX,
                                          win_mutex_sweep)

    if pid == 0:
        client.key_value_set(
            _WIN_MUTEX_PREFIX + "lease_probe",
            f"999:1:1{_LEASE_MARK}{time.time() - 5.0:.3f}")
    client.wait_at_barrier("lease_start", 30_000)
    if pid == 1:
        t0 = time.monotonic()
        with win_mutex("lease_probe", timeout_s=15):
            pass  # stolen from the dead owner automatically
        # expected ~2-3s: the contender must watch the value stay
        # unchanged for the confirmation window before it may steal
        assert time.monotonic() - t0 < 12, "auto-recovery took too long"
    client.wait_at_barrier("lease_mid", 60_000)

    # 8b. a live holder with a SHORT lease and a LONGER critical section is
    # never stolen: the heartbeat out-refreshes the lease (and every
    # refresh resets contenders' unchanged-value confirmation clocks).
    if pid == 0:
        with win_mutex("live_probe", lease_s=3.0):
            client.wait_at_barrier("live_held", 30_000)
            time.sleep(4.0)  # > one full lease period
        client.wait_at_barrier("live_done", 60_000)
    else:
        client.wait_at_barrier("live_held", 30_000)
        try:
            with win_mutex("live_probe", timeout_s=1.5):
                raise AssertionError("stole a LIVE holder's lock")
        except TimeoutError:
            pass
        client.wait_at_barrier("live_done", 60_000)
        with win_mutex("live_probe", timeout_s=10):
            pass  # released normally: acquirable
    client.wait_at_barrier("live_end", 60_000)

    # 9. sweep clears exactly the expired-lease keys
    if pid == 0:
        now = time.time()
        client.key_value_set(_WIN_MUTEX_PREFIX + "sweep_a",
                             f"9:1:1{_LEASE_MARK}{now - 60:.3f}")
        client.key_value_set(_WIN_MUTEX_PREFIX + "sweep_b",
                             f"9:2:2{_LEASE_MARK}{now - 60:.3f}")
        client.key_value_set(_WIN_MUTEX_PREFIX + "sweep_live",
                             f"9:3:3{_LEASE_MARK}{now + 600:.3f}")
        removed = win_mutex_sweep()
        assert removed == 2, f"sweep removed {removed}, expected 2"
        # the unexpired key survived
        assert client.key_value_try_get(_WIN_MUTEX_PREFIX + "sweep_live")
        client.key_value_delete(_WIN_MUTEX_PREFIX + "sweep_live")
    client.wait_at_barrier("sweep_end", 60_000)

    print(f"MP_WORKER_OK {pid}", flush=True)


if __name__ == "__main__":
    main()
