"""What the chip bring-up (PR 22) rests on, checked without a chip: the
worker environment of a lease, the compile-cache rule, dispatch that does
not hide the device, and ``chip_smoke.py``'s refusal to pass off the TPU.
"""

import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.core import compile_cache, tpu_detect
from ray_tpu.core.node_agent import NodeAgent
from ray_tpu.core.resources import ResourceInstanceSet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------- worker environment (B)
def _lease_env(host_chips, chips, env=None):
    agent = types.SimpleNamespace(
        instances=ResourceInstanceSet({"TPU": host_chips})
    )
    env = dict(env or {})
    NodeAgent._apply_chip_isolation(
        agent, env, {"TPU": chips} if chips else {}
    )
    return env


@pytest.mark.parametrize(
    "chips, expected",
    [
        # no chips: the CPU platform, always — whatever the caller asked
        ([], {"JAX_PLATFORMS": "cpu"}),
        # part of the host: the chips and their bounds, platform untouched
        ([2], {"TPU_VISIBLE_CHIPS": "2",
               "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
               "TPU_HOST_BOUNDS": "1,1,1"}),
        ([0, 1], {"TPU_VISIBLE_CHIPS": "0,1",
                  "TPU_CHIPS_PER_HOST_BOUNDS": "2,1,1",
                  "TPU_HOST_BOUNDS": "1,1,1"}),
        # the whole host keeps the host's own bounds
        ([0, 1, 2, 3], {"TPU_VISIBLE_CHIPS": "0,1,2,3"}),
    ],
    ids=["chipless", "one-of-four", "two-of-four", "four-of-four"],
)
def test_lease_environment_on_a_four_chip_host(chips, expected):
    assert _lease_env(4, chips) == expected


def test_chipless_lease_overrides_a_requested_platform():
    env = _lease_env(4, [], {"JAX_PLATFORMS": "tpu", "X": "1"})
    assert env == {"JAX_PLATFORMS": "cpu", "X": "1"}


def test_one_chip_host_lease_sets_no_bounds():
    assert _lease_env(1, [0]) == {"TPU_VISIBLE_CHIPS": "0"}


@pytest.mark.parametrize(
    "env, holds",
    [
        ({}, False),
        ({"TPU_VISIBLE_CHIPS": "0"}, True),
        ({"TPU_VISIBLE_CHIPS": "0", "JAX_PLATFORMS": "tpu,cpu"}, True),
        # a CPU test cluster that declares TPUs it does not have
        ({"TPU_VISIBLE_CHIPS": "0", "JAX_PLATFORMS": "cpu"}, False),
    ],
    ids=["no-lease", "lease", "lease-tpu-listed", "lease-cpu-pinned"],
)
def test_lease_holds_chips(monkeypatch, env, holds):
    for name in ("TPU_VISIBLE_CHIPS", "JAX_PLATFORMS"):
        monkeypatch.delenv(name, raising=False)
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    assert tpu_detect.lease_holds_chips() is holds


def test_chip_lease_worker_off_the_tpu_is_an_error(monkeypatch):
    """This process's jax is up on the CPU: for a worker whose lease holds
    chips that is an error raised in the worker, not a CPU run."""
    jnp.zeros(1).block_until_ready()  # a backend is initialised
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")
    with pytest.raises(RuntimeError, match="came up on 'cpu'"):
        tpu_detect.leased_platform_verified()


def _grid_env(monkeypatch, visible, host_chips, peers, rank=1):
    for name in list(os.environ):
        if name.startswith("TPU_") or name == "CLOUD_TPU_TASK_ID":
            monkeypatch.delenv(name)
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", visible)
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "1,1,1")
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", str(host_chips))
    tpu_detect.join_host_process_grid(rank, peers)
    return {k: v for k, v in os.environ.items()
            if k.startswith("TPU_") or k == "CLOUD_TPU_TASK_ID"}


def test_four_one_chip_workers_get_the_process_grid(monkeypatch):
    peers = [f"10.0.0.1:{9000 + i}" for i in range(4)]
    assert _grid_env(monkeypatch, "2", 4, peers) == {
        "TPU_VISIBLE_CHIPS": "2",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "2,2,1",
        "TPU_PROCESS_ADDRESSES": ",".join(peers),
        "TPU_PROCESS_PORT": "9001",
        "CLOUD_TPU_TASK_ID": "1",
    }


def test_whole_host_workers_are_left_alone(monkeypatch):
    peers = ["10.0.0.1:9000", "10.0.0.2:9000"]
    assert _grid_env(monkeypatch, "0,1,2,3", 4, peers) == {
        "TPU_VISIBLE_CHIPS": "0,1,2,3",
        "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
    }


def test_unsupported_sub_host_gang_fails_loudly(monkeypatch):
    with pytest.raises(RuntimeError, match="cannot join 2 workers"):
        _grid_env(monkeypatch, "0", 4, ["10.0.0.1:9000", "10.0.0.1:9001"])


# ------------------------------------------------------ compile cache (D)
def _cache_dir_in_child(env_value):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    if env_value is not None:
        env[compile_cache.ENV_VAR] = env_value
    env["PYTHONPATH"] = REPO
    code = (
        "import os, ray_tpu, jax; "
        f"print(os.environ['{compile_cache.ENV_VAR}']); "
        "print(jax.config.jax_compilation_cache_dir)"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=120, cwd="/",
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_cache_dir_set_outside_is_untouched(tmp_path):
    assert _cache_dir_in_child(str(tmp_path)) == [str(tmp_path)] * 2


def test_cache_dir_unset_is_one_fixed_path_in_the_checkout():
    fixed = os.path.join(REPO, ".jax_cache")
    assert _cache_dir_in_child(None) == [fixed, fixed]
    assert _cache_dir_in_child(None) == [fixed, fixed]  # a second process


def test_cache_dir_reaches_a_jax_imported_first(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(jax.config, "update", lambda *a: seen.append(a))
    seen = []
    assert compile_cache.place_compile_cache() == compile_cache.DEFAULT_DIR
    assert seen == [("jax_compilation_cache_dir", compile_cache.DEFAULT_DIR)]
    assert os.environ[compile_cache.ENV_VAR] == compile_cache.DEFAULT_DIR


# ------------------------------------------ no fallback hides the device (C)
def test_flash_on_tpu_raises_where_the_blocks_do_not_divide(monkeypatch):
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    q = jnp.zeros((1, 640, 2, 64), jnp.bfloat16)  # 640 % 512 != 0
    with pytest.raises(ValueError, match="not multiples of the blocks"):
        attention.flash_attention(q, q, q)
    # the explicit way to the reference stays open
    out = attention.flash_attention(q, q, q, force_reference=True)
    assert out.shape == q.shape


def test_flash_off_tpu_unforced_is_the_reference():
    from ray_tpu.ops import attention

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 640, 2, 64))
    assert jnp.array_equal(
        attention.flash_attention(q, q, q),
        attention.reference_attention(q, q, q, causal=True),
    )


# ------------------------------------------------------------ chip_smoke.py
def _smoke(args, cwd=REPO, script=None):
    return subprocess.run(
        [sys.executable, script or os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=600, cwd=cwd,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )


def test_chip_smoke_fails_without_a_chip():
    out = _smoke([])
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    script = tmp_path / "chip_smoke.py"
    script.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    out = _smoke([], cwd=str(tmp_path), script=str(script))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_chip_smoke_cpu_rehearsal_walks_every_phase():
    """Tiny widths on CPU workers, chosen only by the flag; it reports
    ``rehearsal_ok`` and never ``ok``."""
    out = _smoke(["--rehearse-cpu"])
    assert out.returncode == 0, (out.stdout + out.stderr)[-4000:]
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith('{"rehearsal_ok": true')
    assert '"platform": "cpu"' in last
    assert '"ok"' not in out.stdout


# ------------------------------- what the chip runs broke in the core (PR 22)
def _wait_tpu_available(want, timeout=30):
    import time

    import ray_tpu

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if ray_tpu.available_resources().get("TPU", 0) == want:
            return
        time.sleep(0.2)
    raise AssertionError(
        f"TPU available never read {want}: {ray_tpu.available_resources()}"
    )


def test_removed_bundle_keeps_a_live_leases_share_charged():
    """A TPU process takes a long while to let go of its chips.  Until its
    lease ends, a removed placement group must not hand those chips back:
    the node would advertise chips whose instances are still taken."""
    import time

    import ray_tpu
    from ray_tpu.core.placement import (
        placement_group, placement_group_strategy, remove_placement_group,
    )

    ray_tpu.init(num_cpus=4, resources={"TPU": 2})
    try:
        @ray_tpu.remote
        class Holder:
            def chips(self):
                return os.environ.get("TPU_VISIBLE_CHIPS")

        pg = placement_group([{"CPU": 1, "TPU": 1}])
        pg.ready(timeout=60)
        holder = Holder.options(
            num_cpus=1, num_tpus=1,
            scheduling_strategy=placement_group_strategy(pg, 0),
        ).remote()
        assert ray_tpu.get(holder.chips.remote(), timeout=60) == "0"
        _wait_tpu_available(1)  # the view is a heartbeat behind
        remove_placement_group(pg)
        end = time.monotonic() + 3.0
        while time.monotonic() < end:  # the holder lives: one chip is free
            assert ray_tpu.available_resources().get("TPU", 0) <= 1
            time.sleep(0.2)
        ray_tpu.kill(holder)
        _wait_tpu_available(2)
    finally:
        ray_tpu.shutdown()


def test_late_heartbeat_of_a_live_agent_is_not_node_death():
    """The control plane pings before it buries: an agent that stood still
    for longer than the heartbeat limit (a TPU runtime starting up held the
    v5e host's agent for 10 s) answers once it runs again, and stays."""
    import signal
    import time

    import ray_tpu
    from ray_tpu import api

    ray_tpu.init(
        num_cpus=2, _system_config={"health_check_timeout_s": 3.0}
    )
    try:
        agent = api._local_node.pg.procs[1]
        os.kill(agent.pid, signal.SIGSTOP)
        time.sleep(4.0)
        os.kill(agent.pid, signal.SIGCONT)
        time.sleep(4.0)  # sweeps run; a buried node would stay buried

        @ray_tpu.remote
        def f():
            return 7

        assert ray_tpu.get(f.remote(), timeout=60) == 7
        assert all(n.get("alive", True) for n in ray_tpu.nodes())
    finally:
        ray_tpu.shutdown()


def test_late_heartbeat_of_a_live_driver_is_not_job_death():
    """Drivers get the agents' treatment: a driver whose heartbeats stood
    still for longer than the limit (the same stall, on the v5e host, cost
    one serving run in 26 its job while the replica's TPU runtime came up)
    answers the control plane's ping, and its job and actors stay."""
    import time

    import ray_tpu
    from ray_tpu.core.core_worker import global_worker

    ray_tpu.init(
        num_cpus=2, _system_config={"health_check_timeout_s": 3.0}
    )
    try:
        @ray_tpu.remote
        class Keeper:
            def ping(self):
                return 7

        keeper = Keeper.remote()
        assert ray_tpu.get(keeper.ping.remote(), timeout=60) == 7
        w = global_worker()
        w.loop.call_soon_threadsafe(w._heartbeat_task.cancel)
        time.sleep(5.5)  # no heartbeat for 3 s + a sweep or two
        w.loop.call_soon_threadsafe(
            lambda: setattr(w, "_heartbeat_task",
                            w.loop.create_task(w._job_heartbeat_loop())))
        time.sleep(2.0)  # a lost job's actors would be gone by now
        assert ray_tpu.get(keeper.ping.remote(), timeout=60) == 7
        jobs = w._run_sync(w.cp.call("list_jobs", {}))
        assert jobs[w.job_id]["state"] == "RUNNING"
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("ignores_sigterm", [False, True])
def test_shutdown_returns_with_its_workers_gone(ignores_sigterm):
    """``shutdown()`` leaves no process behind: the agent kills and reaps
    its workers before it exits (a TPU worker is slow to let go of its
    chip; one that joined ``jax.distributed`` ignores SIGTERM), and the
    node waits for the agent.  No orphan, no zombie."""
    import ray_tpu

    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        class Holder:
            def pid(self, ignore):
                if ignore:  # libc: python's signal() is main-thread only
                    import ctypes
                    import signal

                    ctypes.CDLL(None).signal(int(signal.SIGTERM), 1)  # SIG_IGN
                return os.getpid()

        holder = Holder.remote()
        pid = ray_tpu.get(holder.pid.remote(ignores_sigterm), timeout=60)
        assert os.path.exists(f"/proc/{pid}")
    finally:
        ray_tpu.shutdown()
    assert not os.path.exists(f"/proc/{pid}")
