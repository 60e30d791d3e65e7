"""`ray-tpu stack`: live await-chain dumps from system processes
(reference: `ray stack`, scripts/scripts.py:2011 — py-spy there; SIGUSR1
handlers installed by core/stack_dump.py here)."""

import os
import signal
import time

import pytest

import ray_tpu
import ray_tpu.api as api


def _session_log(name_part):
    """A log of THIS test's cluster: the newest session directory under
    /tmp/ray_tpu may be another xdist worker's, started a moment ago."""
    import glob

    session = api._local_node.log_dir
    logs = glob.glob(os.path.join(session, f"*{name_part}*.log"))
    assert logs, f"no {name_part} log in {session}"
    return max(logs, key=os.path.getmtime)


def test_sigusr1_dumps_await_chains():
    ray_tpu.init(num_cpus=2)
    try:
        # Force a worker into existence (and keep the cluster busy enough
        # to have interesting tasks).
        @ray_tpu.remote
        def f():
            return os.getpid()

        worker_pid = ray_tpu.get(f.remote(), timeout=60)

        agent_proc = api._local_node.pg.procs[1]  # [cp, agent]
        os.kill(agent_proc.pid, signal.SIGUSR1)
        os.kill(worker_pid, signal.SIGUSR1)

        deadline = time.monotonic() + 10
        agent_log = _session_log("node_agent")
        worker_log = None
        while time.monotonic() < deadline:
            text = open(agent_log, errors="replace").read()
            try:
                worker_log = _session_log("worker-")
                wtext = open(worker_log, errors="replace").read()
            except AssertionError:
                wtext = ""
            if "asyncio tasks" in text and "asyncio tasks" in wtext:
                break
            time.sleep(0.3)
        assert "asyncio tasks" in text, "agent produced no dump"
        assert "_read_loop" in text or "_on_connection" in text
        assert "asyncio tasks" in wtext, "worker produced no dump"
        # The worker dump includes the exec-pipeline cursor line.
        assert "exec pipeline:" in wtext
    finally:
        ray_tpu.shutdown()


def test_stack_cli_lists_processes():
    from ray_tpu.scripts.cli import build_parser

    ray_tpu.init(num_cpus=1)
    try:
        parser = build_parser()
        args = parser.parse_args(["stack", "--wait", "1.5"])
        assert args.fn(args) == 0
    finally:
        ray_tpu.shutdown()
