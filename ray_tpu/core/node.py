"""Node/process supervisor: starts and monitors the per-node system processes.

Equivalent of the reference's Node class + services (ray
``python/ray/_private/node.py``, ``services.py``): the head path spawns the
control plane, every node spawns a node agent; processes log to the session
directory and are killed as a group on shutdown.  Also provides the
in-process multi-node ``Cluster`` test fixture (the reference's key testing
trick, ray ``python/ray/cluster_utils.py:135``): multiple node agents on one
machine, each believing it is a distinct node.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

from . import shm
from .config import GlobalConfig
from .rpc import RpcClient, find_free_port

_HEAD_INFO_FILE = "/tmp/ray_tpu/head_info.json"
# Node.stop(): the agent reaps its workers first (node_agent._WORKER_REAP_S).
_STOP_WAIT_S = 100.0


def _wait_for_server(address: str, timeout: float = 30.0) -> None:
    """Block until an RpcServer answers ping at address."""

    async def try_ping():
        client = RpcClient(address)
        await client.connect()
        reply = await client.call("ping", timeout=2)
        await client.close()
        return reply == "pong"

    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            if asyncio.run(try_ping()):
                return
        except Exception as e:  # noqa: BLE001
            last = e
        time.sleep(0.05)
    raise TimeoutError(f"server at {address} did not come up: {last}")


class ProcessGroup:
    def __init__(self):
        self.procs: List[subprocess.Popen] = []
        self.die_with_parent = False

    def spawn(self, argv: List[str], log_path: str, env: Optional[dict] = None):
        full_env = dict(os.environ)
        if env:
            full_env.update(env)
        full_env.update(GlobalConfig.overrides_as_env())
        # Log lines and `ray-tpu stack` dumps must reach the file when
        # they happen — block-buffered stdio leaves a killed process's
        # log empty.
        full_env["PYTHONUNBUFFERED"] = "1"
        if self.die_with_parent:
            # System processes watch this pid and self-exit when it dies —
            # a SIGKILLed driver must not leave an orphaned cluster behind
            # (reference precedent: ray's process reaper).
            full_env["RAY_TPU_PARENT_PID"] = str(os.getpid())
        else:
            full_env.pop("RAY_TPU_PARENT_PID", None)
        out = open(log_path, "ab")

        def ignore_usr1():
            # `ray-tpu stack` uses SIGUSR1; ignored dispositions survive
            # exec, so a signal during the child's import phase (before
            # its loop installs the dump handler) is dropped instead of
            # killing the starting process.
            import signal

            signal.signal(signal.SIGUSR1, signal.SIG_IGN)

        proc = subprocess.Popen(
            argv, stdout=out, stderr=subprocess.STDOUT, env=full_env,
            start_new_session=True, preexec_fn=ignore_usr1,
        )
        self.procs.append(proc)
        return proc

    def kill_all(self, timeout_s: float = 3.0):
        """SIGTERM, then SIGKILL what outlives ``timeout_s``.  A node agent
        stops its workers and waits for them to be gone before it exits,
        so a caller that must leave no process behind gives it the time
        (``Node.stop``); an agent cut short leaves its workers to their
        own watchdog."""
        for proc in self.procs:
            try:
                os.killpg(os.getpgid(proc.pid), signal.SIGTERM)
            except (ProcessLookupError, PermissionError):
                pass
        deadline = time.monotonic() + timeout_s
        for proc in self.procs:
            try:
                proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
                    proc.wait(timeout=10)  # no zombie left either
                except (ProcessLookupError, PermissionError,
                        subprocess.TimeoutExpired):
                    pass
        self.procs.clear()


class Node:
    """Manages the system processes for one logical node (and, on the head,
    the control plane)."""

    def __init__(
        self,
        head: bool,
        cp_address: Optional[str] = None,
        resources: Optional[Dict[str, float]] = None,
        labels: Optional[Dict[str, str]] = None,
        session_id: Optional[str] = None,
        num_cpus: Optional[float] = None,
        port: Optional[int] = None,
        die_with_parent: bool = False,
        ha_dir: Optional[str] = None,
    ):
        self.head = head
        self.port = port
        self.session_id = session_id or shm.new_session_id()
        self.log_dir = os.path.join(
            tempfile.gettempdir(), "ray_tpu", f"session_{self.session_id}"
        )
        os.makedirs(self.log_dir, exist_ok=True)
        self.pg = ProcessGroup()
        self.pg.die_with_parent = die_with_parent
        self.cp_address = cp_address
        self.agent_address: Optional[str] = None
        self._cp_argv: Optional[List[str]] = None
        self._cp_log: Optional[str] = None
        self._cp_env: Optional[dict] = None
        # HA (GlobalConfig.cp_ha): the shared lease/journal directory and
        # the CP candidate processes contending over it (head only; a
        # joining node receives ha_dir so its agent can follow failovers).
        self.ha_dir = ha_dir
        self._cp_candidates: List[dict] = []

        # Detection runs through the accelerator plugin registry (TPU is
        # built in; other vendors contribute by registering a manager).
        from .accelerators import all_accelerator_managers

        detected_res: Dict[str, float] = {}
        detected_labels: Dict[str, str] = {}
        for mgr in all_accelerator_managers():
            if mgr.resource_name == "CPU":
                continue  # CPU count is handled below (num_cpus override)
            n = mgr.get_current_node_num_accelerators()
            if n > 0:
                detected_res[mgr.resource_name] = float(n)
            detected_res.update(mgr.get_current_node_additional_resources())
            detected_labels.update(mgr.get_current_node_labels())
        res: Dict[str, float] = {
            "CPU": float(num_cpus if num_cpus is not None else (os.cpu_count() or 1)),
        }
        res.update(detected_res)
        if resources:
            res.update(resources)
        self.resources = res
        lbls = dict(detected_labels)
        if labels:
            lbls.update(labels)
        self.labels = lbls

    def start(self):
        env = {"RAY_TPU_LOG_DIR": self.log_dir}
        if self.head:
            if GlobalConfig.cp_ha:
                self._start_cp_candidates(env)
            else:
                cp_port = self.port or find_free_port()
                self.cp_address = f"127.0.0.1:{cp_port}"
                self._cp_argv = [
                    sys.executable, "-m", "ray_tpu.core.control_plane",
                    "--port", str(cp_port),
                    "--session-id", self.session_id,
                ]
                if GlobalConfig.cp_persistence:
                    self._cp_argv += [
                        "--store-path",
                        os.path.join(self.log_dir, "control_plane.sqlite"),
                    ]
                self._cp_log = os.path.join(self.log_dir, "control_plane.log")
                self._cp_env = dict(env)
                self.pg.spawn(self._cp_argv, self._cp_log, env)
                _wait_for_server(self.cp_address)
        assert self.cp_address
        if self.ha_dir:
            # Inherited by every child this node spawns (ProcessGroup
            # copies os.environ), so workers and the driver build their
            # CP clients with the leader-endpoint resolver.
            os.environ["RAY_TPU_CP_HA_DIR"] = self.ha_dir
        agent_port = find_free_port()
        self.agent_address = f"127.0.0.1:{agent_port}"
        agent_argv = [
            sys.executable, "-m", "ray_tpu.core.node_agent",
            "--port", str(agent_port),
            "--cp-address", self.cp_address,
            "--session-id", self.session_id,
            # The head's agent owns session-wide shm cleanup on
            # parent-death; worker/client agents must never delete the
            # shared arena (same ownership rule as Node.stop()).
            "--owns-session-shm", "1" if self.head else "0",
            "--resources", json.dumps(self.resources),
            "--labels", json.dumps(self.labels),
        ]
        if self.ha_dir:
            agent_argv += ["--cp-ha-dir", self.ha_dir]
        self.pg.spawn(
            agent_argv,
            os.path.join(self.log_dir, "node_agent.log"),
            env,
        )
        _wait_for_server(self.agent_address)
        if self.head:
            os.makedirs(os.path.dirname(_HEAD_INFO_FILE), exist_ok=True)
            with open(_HEAD_INFO_FILE, "w") as f:
                json.dump(
                    {
                        "cp_address": self.cp_address,
                        "session_id": self.session_id,
                        "ha_dir": self.ha_dir,
                    },
                    f,
                )
        return self

    # ------------------------------------------------------------ HA head
    def _start_cp_candidates(self, env: dict, count: int = 2):
        """Spawn ``count`` control-plane candidates over one shared HA
        directory; whichever wins the leader lease serves, the rest tail
        the journal as warm standbys."""
        self.ha_dir = os.path.join(self.log_dir, "cp_ha")
        os.makedirs(self.ha_dir, exist_ok=True)
        for i in range(count):
            self._spawn_cp_candidate(i, env)
        self.cp_address = self._wait_for_leader()

    def _spawn_cp_candidate(self, index: int, env: dict):
        port = find_free_port()
        argv = [
            sys.executable, "-m", "ray_tpu.core.control_plane",
            "--port", str(port),
            "--session-id", self.session_id,
            "--ha-dir", self.ha_dir,
        ]
        log = os.path.join(self.log_dir, f"control_plane_{index}.log")
        proc = self.pg.spawn(argv, log, env)
        cand = {
            "proc": proc,
            "address": f"127.0.0.1:{port}",
            "argv": argv,
            "log": log,
            "env": dict(env),
            "index": index,
        }
        if index < len(self._cp_candidates):
            self._cp_candidates[index] = cand
        else:
            self._cp_candidates.append(cand)
        return cand

    def _wait_for_leader(self, timeout: float = 30.0) -> str:
        """Block until a candidate published the leader endpoint AND
        answers ping there."""
        from .cp_ha import read_endpoint

        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            info = read_endpoint(self.ha_dir)
            if info and info.get("address"):
                try:
                    _wait_for_server(info["address"], timeout=2.0)
                    return info["address"]
                except TimeoutError as e:
                    last = e  # leader died between publish and now
            time.sleep(0.05)
        raise TimeoutError(f"no control-plane leader elected: {last}")

    def leader_epoch(self) -> int:
        from .cp_ha import read_endpoint

        info = read_endpoint(self.ha_dir) if self.ha_dir else None
        return info.get("epoch", 0) if info else 0

    def kill_leader(self) -> int:
        """``kill -9`` the current leader candidate; returns the epoch it
        served under (pass to ``wait_for_failover``)."""
        assert self.head and self._cp_candidates, "HA head required"
        from .cp_ha import read_endpoint

        info = read_endpoint(self.ha_dir) or {}
        leader_address = info.get("address")
        epoch = info.get("epoch", 0)
        for cand in self._cp_candidates:
            if cand["address"] == leader_address and cand["proc"].poll() is None:
                cand["proc"].kill()
                cand["proc"].wait(timeout=10)
                return epoch
        raise RuntimeError(f"no live candidate serves {leader_address}")

    def wait_for_failover(self, old_epoch: int, timeout: float = 30.0) -> str:
        """Block until a NEWER leader (epoch > old_epoch) serves; updates
        and returns ``cp_address``."""
        from .cp_ha import read_endpoint

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            info = read_endpoint(self.ha_dir)
            if info and info.get("epoch", 0) > old_epoch and info.get("address"):
                try:
                    _wait_for_server(info["address"], timeout=2.0)
                    self.cp_address = info["address"]
                    return info["address"]
                except TimeoutError:
                    pass
            time.sleep(0.05)
        raise TimeoutError(
            f"no failover past epoch {old_epoch} within {timeout}s"
        )

    def ensure_standby(self):
        """Respawn any dead candidate so the cluster regains a warm
        standby after a failover (the chaos injector's revert)."""
        assert self.head and self.ha_dir
        for cand in list(self._cp_candidates):
            if cand["proc"].poll() is not None:
                try:
                    self.pg.procs.remove(cand["proc"])
                except ValueError:
                    pass
                self._spawn_cp_candidate(cand["index"], cand["env"])

    def kill_control_plane(self):
        """Hard-kill the control-plane process (head nodes only) — the
        GCS-crash half of the restart-FT test story."""
        assert self.head, "control plane runs on the head node"
        assert not self._cp_candidates, "HA mode: use kill_leader()"
        proc = self.pg.procs[0]
        proc.kill()
        proc.wait(timeout=10)

    def restart_control_plane(self):
        """Restart the control plane on the same port; with persistence on,
        it reloads its tables and agents/drivers reconnect (reference:
        python/ray/tests/test_gcs_fault_tolerance.py)."""
        assert self.head and self._cp_argv is not None
        proc = self.pg.procs[0]
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
        self.pg.spawn(self._cp_argv, self._cp_log, self._cp_env)
        # The new process replaces slot 0 so kill ordering stays stable.
        self.pg.procs[0] = self.pg.procs.pop()
        _wait_for_server(self.cp_address)

    def stop(self):
        # The HA discovery env var must die with the node that exported
        # it: a later non-HA init in this process would otherwise build
        # resolvers on this (now dead) session's endpoint record.
        if self.ha_dir and os.environ.get("RAY_TPU_CP_HA_DIR") == self.ha_dir:
            del os.environ["RAY_TPU_CP_HA_DIR"]
        self.pg.kill_all(timeout_s=_STOP_WAIT_S)
        from .object_store import drop_arena

        drop_arena(self.session_id)
        if self.head:
            # Session-wide shm (arena + segments) belongs to the HEAD's
            # lifetime: a worker/client node leaving must not delete the
            # store out from under every other node in the session.
            shm.cleanup_session(self.session_id)


class Cluster:
    """In-process multi-node test cluster: one control plane + N node agents
    on this machine (ray ``cluster_utils.Cluster`` analog).  Nodes can be
    added and killed freely to exercise fault-tolerance paths."""

    def __init__(self):
        self.head_node: Optional[Node] = None
        self.worker_nodes: List[Node] = []

    @property
    def cp_address(self) -> str:
        assert self.head_node is not None
        return self.head_node.cp_address  # type: ignore[return-value]

    def add_node(self, num_cpus: float = 1, resources=None, labels=None) -> Node:
        if self.head_node is None:
            node = Node(
                head=True, resources=resources, labels=labels,
                num_cpus=num_cpus, die_with_parent=True,
            )
            node.start()
            self.head_node = node
        else:
            node = Node(
                head=False,
                cp_address=self.cp_address,
                resources=resources,
                labels=labels,
                session_id=self.head_node.session_id,
                num_cpus=num_cpus,
                die_with_parent=True,
            )
            node.start()
            self.worker_nodes.append(node)
        return node

    def kill_node(self, node: Node):
        node.pg.kill_all()
        if node in self.worker_nodes:
            self.worker_nodes.remove(node)

    def shutdown(self):
        for node in self.worker_nodes:
            node.stop()
        self.worker_nodes.clear()
        if self.head_node:
            self.head_node.stop()
            self.head_node = None


def read_head_info() -> Optional[dict]:
    try:
        with open(_HEAD_INFO_FILE) as f:
            return json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        return None
