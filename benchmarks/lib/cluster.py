"""One ray_tpu cluster on this host, and nothing left behind.

Copied from ``chip_smoke.py`` (chip-proven in PR 22) so that later PRs can
change the program without changing the yardstick.  The process that calls
these never initialises a jax backend: a chip belongs to the worker that
holds its lease.
"""

from __future__ import annotations

import ctypes
import glob
import os
import signal
import sys
import time

LEASE_WAIT_S = 120
EXIT_WAIT_S = 30


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def start_cluster(chips: int, rehearse: bool) -> None:
    import ray_tpu
    from ray_tpu.core import tpu_detect

    if rehearse:
        ray_tpu.init(num_cpus=8, resources={"TPU": chips})
    else:
        found = tpu_detect.num_local_chips()
        check(found >= chips,
              f"this cell needs {chips} TPU chip(s); {found} detected")
        ray_tpu.init()  # resources auto-detected
    total = ray_tpu.cluster_resources().get("TPU", 0)
    check(total >= chips, f"node registered TPU={total}, cell needs {chips}")


def print_worker_logs(limit: int = 3000) -> None:
    """On failure: the session's logs vanish with the machine."""
    from ray_tpu import api

    node = api._local_node
    if node is None:
        return
    paths = sorted(glob.glob(os.path.join(node.log_dir, "*.log")),
                   key=os.path.getmtime)[-8:]
    for path in paths:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - limit))
            tail = f.read().decode("utf-8", "replace")
        print(f"----- tail of {path}\n{tail}", file=sys.stderr, flush=True)


def adopt_orphans() -> None:
    """Workers run in sessions of their own under the node agent; should
    one outlive it, it becomes this process's child, so the census sees it."""
    PR_SET_CHILD_SUBREAPER = 36
    check(ctypes.CDLL(None, use_errno=True).prctl(
        PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0) == 0, "prctl(subreaper) failed")


def children() -> list:
    out = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
            with open(f"/proc/{pid}/cmdline") as f:
                cmd = f.read().replace("\0", " ").strip()
        except OSError:
            continue  # gone meanwhile
        state, ppid = stat[stat.rindex(")") + 2:].split()[:2]
        if int(ppid) == os.getpid():
            out.append((int(pid), state, cmd[:120]))
    return out


def stop_everything() -> list:
    """``serve.shutdown()`` + ``ray_tpu.shutdown()``, then a census: what
    shutdown left running is killed and reaped here, and returned."""
    import ray_tpu
    from ray_tpu import serve

    try:
        if ray_tpu.is_initialized():
            serve.shutdown()
    finally:
        ray_tpu.shutdown()

    def reap():
        try:
            while os.waitpid(-1, os.WNOHANG)[0]:
                pass
        except ChildProcessError:
            pass

    reap()
    leaked = [c for c in children() if c[1] != "Z"]
    for pid, _state, cmd in leaked:
        log(f"LEFT RUNNING by ray_tpu.shutdown(): pid {pid}: {cmd}")
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    deadline = time.monotonic() + EXIT_WAIT_S
    while children() and time.monotonic() < deadline:
        reap()
        time.sleep(0.1)
    return leaked + children()
