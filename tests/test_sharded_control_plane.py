"""Sharded control plane: multi-lane RPC service, owner-table sharding,
and batched placement-group commits (PR 6).

Unit layers (no cluster): lane pinning + per-connection ordering on the
multi-lane RpcServer, ForwardToPrimary punts, OwnerTable shard routing.
Cluster layers: owner-shard hit/miss/owner-death through real borrows,
batched PG commit atomicity (whole-group rollback on partial failure,
sibling independence), group-commit coalescing under concurrent creates,
cancel racing a reply with lanes forced on, and the acceptance check that
per-lane telemetry reaches the flight recorder / prometheus_text().
"""

import asyncio
import threading
import time

import pytest

import ray_tpu
from ray_tpu.core.ids import ObjectID
from ray_tpu.core.owner_table import OwnerTable
from ray_tpu.core.rpc import ForwardToPrimary, RpcClient, RpcServer


# --------------------------------------------------------------- rpc lanes
class _LaneHandler:
    LANE_SAFE_METHODS = frozenset({"fast"})

    def __init__(self):
        self.closed = 0

    def handle_fast(self, payload, conn):
        if payload.get("punt"):
            async def slow():
                await asyncio.sleep(0.002)
                return ("primary", payload["i"],
                        threading.current_thread().name)
            return ForwardToPrimary(slow)
        return ("lane", payload["i"], threading.current_thread().name)

    async def handle_stateful(self, payload, conn):
        # NOT lane-safe: must execute on the primary loop's thread.
        return threading.current_thread().name

    def on_connection_closed(self, conn):
        self.closed += 1


class TestMultiLaneServer:
    def _run(self, coro):
        return asyncio.run(coro)

    def test_connection_order_preserved_under_lane_pinning(self):
        """Each connection pins to ONE lane at accept time; replies for a
        connection's calls come back in request order even when fast
        lane-local calls interleave with ForwardToPrimary punts."""

        async def main():
            handler = _LaneHandler()
            srv = RpcServer(handler, lanes=3)
            addr = await srv.start()
            clients = []
            for _ in range(6):
                c = RpcClient(addr)
                await c.connect()
                clients.append(c)
            try:
                for c in clients:
                    outs = await asyncio.gather(*[
                        c.call("fast", {"i": i, "punt": i % 3 == 0})
                        for i in range(40)
                    ])
                    assert [o[1] for o in outs] == list(range(40))
                    # Punted calls ran on the primary thread, fast calls on
                    # the pinned lane's thread — one lane per connection.
                    lane_threads = {o[2] for o in outs if o[0] == "lane"}
                    assert len(lane_threads) == 1
                stats = srv.lane_stats()
                assert sum(s["connections"] for s in stats) == 6
                busy = [s for s in stats if s["frames_total"] > 0]
                assert len(busy) >= 2, f"no lane spread: {stats}"
                assert sum(s["forwarded_total"] for s in stats) > 0
                for c in clients:
                    await c.close()
                # Teardown hooks (forwarded to the primary loop for
                # lane-pinned connections) land asynchronously.
                for _ in range(300):
                    if handler.closed == 6:
                        break
                    await asyncio.sleep(0.01)
                assert handler.closed == 6
            finally:
                await srv.stop()

        self._run(main())

    def test_non_lane_safe_handler_runs_on_primary(self):
        async def main():
            handler = _LaneHandler()
            srv = RpcServer(handler, lanes=2)
            addr = await srv.start()
            # Two connections so at least one lands on a worker lane.
            c1, c2 = RpcClient(addr), RpcClient(addr)
            await c1.connect()
            await c2.connect()
            try:
                main_thread = threading.current_thread().name
                for c in (c1, c2):
                    assert await c.call("stateful", {}) == main_thread
            finally:
                await c1.close()
                await c2.close()
                await srv.stop()

        self._run(main())

    def test_single_lane_server_unchanged(self):
        """lanes=1 keeps the classic single-loop path (no lane threads),
        including ForwardToPrimary handling."""

        async def main():
            handler = _LaneHandler()
            srv = RpcServer(handler, lanes=1)
            addr = await srv.start()
            c = RpcClient(addr)
            await c.connect()
            try:
                out = await c.call("fast", {"i": 7, "punt": True})
                assert out[0] == "primary" and out[1] == 7
                assert len(srv.lane_stats()) == 1
            finally:
                await c.close()
                await srv.stop()

        self._run(main())


# ------------------------------------------------------------- owner table
class TestOwnerTable:
    def _oid(self, i):
        return ObjectID.from_random()

    def test_dict_compatibility_and_routing(self):
        t = OwnerTable(num_shards=4)
        assert t.num_shards == 4
        oids = [ObjectID.from_random() for _ in range(64)]
        for i, oid in enumerate(oids):
            t[oid] = i
        assert len(t) == 64
        for i, oid in enumerate(oids):
            assert oid in t
            assert t[oid] == i
            assert t.get(oid) == i
            # Routing is stable and in-range.
            s = t.shard_index(oid)
            assert 0 <= s < 4 and s == t.shard_index(oid)
        assert sorted(t.values()) == list(range(64))
        assert len(list(t.items())) == 64
        # 64 random ids should not all land on one of 4 shards.
        sizes = t.shard_sizes()
        assert sum(sizes) == 64 and max(sizes) < 64
        assert t.pop(oids[0]) == 0
        assert t.get(oids[0]) is None
        del t[oids[1]]
        assert oids[1] not in t
        assert len(t) == 62

    def test_lookup_counters_per_shard(self):
        t = OwnerTable(num_shards=8)
        oid = ObjectID.from_random()
        t[oid] = "x"
        before = list(t.lookups)
        for _ in range(5):
            t.get(oid)
        deltas = [a - b for a, b in zip(t.lookups, before)]
        assert deltas[t.shard_index(oid)] == 5
        assert sum(deltas) == 5
        assert t.stats()["lookups_total"] == sum(t.lookups)

    def test_rounds_shards_to_power_of_two(self):
        assert OwnerTable(num_shards=3).num_shards == 4
        assert OwnerTable(num_shards=1).num_shards == 1


# ---------------------------------------------------------------- clusters
@pytest.fixture(scope="module")
def cluster():
    ctx = ray_tpu.init(num_cpus=4)
    yield ctx
    ray_tpu.shutdown()


@ray_tpu.remote
class Owner:
    def make(self, n):
        return [ray_tpu.put(i * 10) for i in range(n)]

    def ping(self):
        return "ok"


class TestOwnerShardCluster:
    def test_shard_hit_path_counts_fast_entries(self, cluster):
        """Borrowed batch gets of READY remote objects resolve through the
        owner's shard fast path (no primary-loop punt)."""
        from ray_tpu.core.core_worker import try_global_worker

        w = try_global_worker()
        owner = Owner.remote()
        refs = ray_tpu.get(owner.make.remote(16), timeout=60)
        assert ray_tpu.get(refs, timeout=60) == [i * 10 for i in range(16)]
        # The DRIVER is also an owner service; exercise its fast path
        # directly: a driver-owned READY object resolves without a punt.
        ref = ray_tpu.put(b"local")
        fast_before = w._shard_fast_entries
        entry = w._owner_entry_fast(ref.id)
        assert entry is not None and entry["kind"] in ("inline", "shm")
        assert w.handle_get_object({"object_id": ref.id}, None) is not None
        assert w._shard_fast_entries == fast_before + 1
        ray_tpu.kill(owner)

    def test_shard_miss_forwards_to_primary(self, cluster):
        """A not-yet-READY object punts to the primary loop (the punt IS
        the blocking get semantics) and still resolves correctly."""
        from ray_tpu.core.core_worker import try_global_worker

        w = try_global_worker()

        @ray_tpu.remote
        def slow():
            time.sleep(0.4)
            return "done"

        ref = slow.remote()
        fwd_before = w._shard_forwarded_entries
        out = w.handle_get_object({"object_id": ref.id}, None)
        assert isinstance(out, ForwardToPrimary)
        assert w._shard_forwarded_entries == fwd_before + 1
        assert ray_tpu.get(ref, timeout=60) == "done"

    def test_owner_death_error_entry(self, cluster):
        """An unknown/never-owned object resolves to an ObjectLostError
        entry on the fast path — per shard, owner-death is a first-class
        reply, not a hang."""
        from ray_tpu.core.core_worker import try_global_worker
        from ray_tpu.core.exceptions import ObjectLostError
        from ray_tpu.core.rpc import RpcConnectionError
        from ray_tpu.core.serialization import deserialize_from_bytes

        w = try_global_worker()
        ghost = ObjectID.from_random()
        entry = w._owner_entry_fast(ghost)
        assert entry["kind"] == "error"
        err = deserialize_from_bytes(entry["payload"])
        assert isinstance(err, ObjectLostError)
        # And end to end: refs whose owner worker died fail loudly.
        owner = Owner.remote()
        refs = ray_tpu.get(owner.make.remote(4), timeout=60)
        ray_tpu.kill(owner)
        with pytest.raises(
            (ObjectLostError, RpcConnectionError, ray_tpu.GetTimeoutError,
             Exception)
        ):
            ray_tpu.get(refs, timeout=30)


class TestBatchedPgCommits:
    def test_agent_prepare_batch_per_group_atomic(self, cluster):
        """One batched prepare RPC carrying a fitting group AND an
        oversized group: the oversized group's partial reservation rolls
        back entirely (its first bundle DID fit) while the sibling group
        commits — per-group atomicity inside one batch."""
        from ray_tpu.core.core_worker import try_global_worker
        from ray_tpu.core.ids import PlacementGroupID

        w = try_global_worker()

        def available_cpu():
            st = w._run_sync(w.agent.call("debug_state"))
            return st["resources"]["available"].get("CPU", 0.0)

        before = available_cpu()
        ok_id, big_id = PlacementGroupID.from_random(), PlacementGroupID.from_random()
        res = w._run_sync(w.agent.call(
            "prepare_bundles_batch",
            {"groups": [
                {"pg_id": ok_id, "bundles": {0: {"CPU": 1}}},
                # First bundle fits; second overflows the node — the
                # whole group must roll back, including bundle 0.
                {"pg_id": big_id, "bundles": {0: {"CPU": 1}, 1: {"CPU": 16}}},
            ]},
        ))
        assert res["results"] == {ok_id: True, big_id: False}
        assert available_cpu() == before - 1  # only the ok group holds
        w._run_sync(w.agent.call(
            "cancel_bundles_batch", {"pg_ids": [ok_id, big_id]}
        ))
        assert available_cpu() == before

    def test_two_phase_partial_failure_rolls_back_whole_group(self):
        """Multi-node two-phase commit: when ONE node's prepare fails, the
        control plane cancels the group's reservations on every node that
        prepared it and re-queues the group — never a half-placed PG."""
        from ray_tpu.core.control_plane import (
            ControlPlane, PlacementGroupEntry,
        )
        from ray_tpu.core.ids import NodeID, PlacementGroupID

        class FakePool:
            def __init__(self, fail_addr):
                self.fail_addr = fail_addr
                self.calls = []

            def get(self, addr, push_handler=None):
                return FakeClient(addr, self)

        class FakeClient:
            def __init__(self, addr, pool):
                self.addr = addr
                self.pool = pool

            async def call(self, method, payload=None, **kw):
                self.pool.calls.append((self.addr, method, payload))
                if method in ("prepare_bundles_batch", "reserve_bundles_batch"):
                    ok = self.addr != self.pool.fail_addr
                    return {
                        "results": {g["pg_id"]: ok for g in payload["groups"]}
                    }
                return True

        async def main():
            cp = ControlPlane(session_id="t")
            pool = FakePool(fail_addr="b:1")
            cp.agent_clients = pool
            snap = {
                "total": {"CPU": 4}, "available": {"CPU": 4}, "labels": {},
                "pending_demands": [], "idle_s": 0.0,
            }
            for nid, addr in ((NodeID.from_random(), "a:1"),
                              (NodeID.from_random(), "b:1")):
                cp.handle_register_node(
                    {"node_id": nid, "agent_address": addr,
                     "snapshot": dict(snap)},
                    None,
                )
            pg_id = PlacementGroupID.from_random()
            entry = PlacementGroupEntry(
                pg_id, [{"CPU": 1}, {"CPU": 1}], "STRICT_SPREAD", ""
            )
            cp.placement_groups[pg_id] = entry
            await cp._schedule_pg_batch([entry])
            assert entry.state == "PENDING"
            assert pg_id in cp._pending_pgs
            assert cp.pg_batch_stats["rollbacks"] == 1
            cancels = [c for c in pool.calls if c[1] == "cancel_bundles_batch"]
            assert cancels, "prepared node was not rolled back"
            assert all(addr == "a:1" for addr, _m, _p in cancels)
            assert not any(
                c[1] == "commit_bundles_batch" for c in pool.calls
            ), "half-failed group must not commit anywhere"
            # drain the _publish/_kick tasks this spawned
            await asyncio.sleep(0)

        asyncio.run(main())

    def test_sibling_groups_do_not_fate_share(self, cluster):
        """Independent groups in one sweep commit independently: an
        infeasible sibling must not roll back a feasible one."""
        from ray_tpu.core.placement import (
            placement_group, remove_placement_group,
        )

        good = placement_group([{"CPU": 0.5}])
        bad = placement_group([{"CPU": 2}, {"CPU": 3}])
        assert good.ready(timeout=60) is True
        assert bad.ready(timeout=2) is False
        remove_placement_group(good)
        remove_placement_group(bad)

    def test_concurrent_creates_coalesce_and_fuse(self, cluster):
        """Creates issued from many threads while a sweep is in flight
        coalesce into group commits; single-node groups take the fused
        prepare+commit RPC."""
        from ray_tpu.core.core_worker import try_global_worker
        from ray_tpu.core.placement import (
            placement_group, remove_placement_group,
        )

        w = try_global_worker()
        before = w._run_sync(w.cp.call("debug_control_plane"))
        pgs = [None] * 12
        errors = []
        # All at once: started one after the other, threads starved of the
        # CPU by the suite's other workers each find the last sweep done.
        together = threading.Barrier(len(pgs))

        def create(i):
            try:
                together.wait(60)
                pgs[i] = placement_group([{"CPU": 0.01}])
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=create, args=(i,), name=f"pg-create-{i}")
            for i in range(len(pgs))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors
        for pg in pgs:
            assert pg is not None and pg.ready(timeout=60)
        after = w._run_sync(w.cp.call("debug_control_plane"))
        stats_b, stats_a = before["pg_batch_stats"], after["pg_batch_stats"]
        # Single-node groups all rode the fused RPC...
        assert (
            stats_a["fused_commits"] - stats_b["fused_commits"] >= len(pgs)
        )
        # ...and fewer sweeps than groups ran (group commit coalesced).
        assert (
            stats_a["batches"] - stats_b["batches"] < len(pgs)
            or stats_a["batched_creates"] > stats_b["batched_creates"]
        )
        for pg in pgs:
            remove_placement_group(pg)

    def test_create_reply_carries_created_state(self, cluster):
        """ready() needs no follow-up poll in the common case: the create
        reply already says CREATED (the group-commit sweep runs before the
        RPC replies)."""
        from ray_tpu.core.placement import (
            placement_group, remove_placement_group,
        )

        pg = placement_group([{"CPU": 0.01}])
        assert pg._created is True
        t0 = time.perf_counter()
        assert pg.ready(timeout=60)
        assert time.perf_counter() - t0 < 0.01  # no RPC, no poll
        remove_placement_group(pg)


class TestLaneTelemetry:
    def test_lane_and_shard_metrics_reach_prometheus(self, cluster):
        """Acceptance: per-lane queue-depth/dispatch telemetry and the
        owner-shard counters appear in the flight recorder registry and
        in prometheus_text()."""
        from ray_tpu.core.core_worker import try_global_worker
        from ray_tpu.util import metrics as _metrics

        w = try_global_worker()
        # Traffic through owner + agent + cp paths.
        owner = Owner.remote()
        refs = ray_tpu.get(owner.make.remote(8), timeout=60)
        ray_tpu.get(refs, timeout=60)
        ray_tpu.kill(owner)
        w._run_sync(w._flush_metrics())
        text = _metrics.prometheus_text()
        assert "ray_tpu_rpc_lane_frames_total" in text
        assert "ray_tpu_rpc_lane_queue_depth" in text
        assert "ray_tpu_rpc_lane_dispatch_wait_s" in text
        assert "ray_tpu_owner_shard_lookups_total" in text

    def test_agent_debug_state_reports_lanes(self, cluster):
        from ray_tpu.core.core_worker import try_global_worker
        from ray_tpu.core.rpc import resolve_service_lanes

        w = try_global_worker()
        rows = w._run_sync(w.agent.call("debug_state"))["rpc_lanes"]
        assert len(rows) == resolve_service_lanes()
        assert all("frames_total" in r and "inflight" in r for r in rows)


class TestCancelRaceUnderLanes:
    # NOTE: runs against its own cluster (lanes forced on for every
    # server, workers included) — keep this class LAST in the file: it
    # tears down the module-scoped cluster first.
    def test_cancel_racing_completed_task_does_not_poison_retry(self):
        """ray_tpu.cancel racing a task whose reply rides another lane:
        the PR-5 executor-side cancel-mark semantics must hold — a cancel
        arriving after the reply is dropped, so later executions of tasks
        on the same worker never get skipped by a stale mark."""
        ray_tpu.shutdown()  # module cluster, if any (lane config differs)
        ray_tpu.init(
            num_cpus=2,
            _system_config={"rpc_service_lanes": 2, "prestart_workers": 2},
        )
        try:
            @ray_tpu.remote
            def quick(i):
                return i

            done = 0
            for i in range(20):
                ref = quick.remote(i)
                value = ray_tpu.get(ref, timeout=60)
                # Reply has landed; the cancel races behind it.
                ray_tpu.cancel(ref)
                assert value == i
                done += 1
            # No stale cancel mark may skip later tasks.
            outs = ray_tpu.get(
                [quick.remote(i) for i in range(30)], timeout=120
            )
            assert outs == list(range(30))
            assert done == 20
        finally:
            ray_tpu.shutdown()
