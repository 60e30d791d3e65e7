"""State API, task events, timeline, CLI, and job submission tests.

Models the reference's state-API tests (ray ``python/ray/tests/
test_state_api*.py``) and job tests (``dashboard/modules/job/tests``).
"""

import json
import sys
import time

import pytest


def _wait_for(pred, timeout=10, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {msg}")


def test_task_events_and_state_api(ray_start_regular):
    import ray_tpu
    from ray_tpu.util.state import (
        list_actors,
        list_nodes,
        list_tasks,
        summarize_actors,
        summarize_tasks,
    )

    @ray_tpu.remote
    def add(a, b):
        return a + b

    @ray_tpu.remote
    def boom():
        raise ValueError("no")

    assert ray_tpu.get(add.remote(1, 2)) == 3
    with pytest.raises(Exception):
        ray_tpu.get(boom.remote())

    def finished_visible():
        tasks = list_tasks()
        states = {(t["name"], t["state"]) for t in tasks}
        return ("add", "FINISHED") in states and ("boom", "FAILED") in states

    _wait_for(finished_visible, msg="task events to flush")

    tasks = list_tasks(filters={"name": "add"})
    assert tasks and all(t["name"] == "add" for t in tasks)
    assert tasks[0]["state_ts"].get("RUNNING") is not None

    summary = summarize_tasks()
    assert summary["by_name"]["add"]["FINISHED"] >= 1
    assert summary["by_name"]["boom"]["FAILED"] >= 1

    nodes = list_nodes()
    assert len(nodes) == 1 and nodes[0]["alive"]

    @ray_tpu.remote
    class Counter:
        def incr(self):
            return 1

    c = Counter.remote()
    assert ray_tpu.get(c.incr.remote()) == 1
    actors = list_actors()
    assert any(a["state"] == "ALIVE" for a in actors)
    assert summarize_actors()["total"] >= 1


def test_timeline_and_profile(ray_start_regular, tmp_path):
    import ray_tpu

    @ray_tpu.remote
    def work():
        time.sleep(0.05)
        return 1

    ray_tpu.get([work.remote() for _ in range(3)])
    with ray_tpu.profile("my_span", {"k": "v"}):
        time.sleep(0.01)

    out = tmp_path / "trace.json"

    def has_events():
        events = ray_tpu.timeline(str(out))
        names = {e["name"] for e in events}
        # A task still RUNNING in the control plane's view has no end yet
        # (dur 0): wait for one whose FINISHED event has arrived too.
        return "my_span" in names and any(
            e["name"] == "work" and e.get("dur", 0) > 0 for e in events)

    _wait_for(has_events, msg="timeline events")
    events = json.loads(out.read_text())
    ev = next(e for e in events if e["name"] == "work" and e["dur"] > 0)
    assert ev["ph"] == "X"


def test_cli_status_and_list(ray_start_regular, capsys):
    from ray_tpu.scripts.cli import main

    import ray_tpu

    @ray_tpu.remote
    def noop():
        return None

    ray_tpu.get(noop.remote())
    assert main(["status"]) == 0
    out = capsys.readouterr().out
    assert "nodes: 1 alive" in out
    assert "CPU" in out

    assert main(["list", "nodes"]) == 0
    assert main(["list", "tasks", "--format", "json"]) == 0
    out = capsys.readouterr().out
    assert "node_id" in out

    assert main(["summary", "actors"]) == 0


def test_cli_timeline(ray_start_regular, tmp_path, capsys):
    import ray_tpu
    from ray_tpu.scripts.cli import main

    @ray_tpu.remote
    def tick():
        return 1

    ray_tpu.get(tick.remote())
    time.sleep(1.2)  # allow flush
    out = tmp_path / "t.json"
    assert main(["timeline", "-o", str(out)]) == 0
    events = json.loads(out.read_text())
    assert isinstance(events, list)


def test_job_submission_end_to_end(ray_start_regular):
    from ray_tpu.job import JobStatus, JobSubmissionClient

    client = JobSubmissionClient()
    sid = client.submit_job(
        entrypoint=f"{sys.executable} -c \"print('job says hi')\"",
    )
    status = client.wait_until_finished(sid, timeout=60)
    assert status == JobStatus.SUCCEEDED
    assert "job says hi" in client.get_job_logs(sid)
    info = client.get_job_info(sid)
    assert info.driver_exit_code == 0
    assert client.list_jobs()
    assert client.delete_job(sid)
    assert client.get_job_info(sid) is None


def test_job_failure_and_stop(ray_start_regular):
    from ray_tpu.job import JobStatus, JobSubmissionClient

    client = JobSubmissionClient()
    sid = client.submit_job(
        entrypoint=f"{sys.executable} -c 'raise SystemExit(3)'",
    )
    assert client.wait_until_finished(sid, timeout=60) == JobStatus.FAILED
    assert client.get_job_info(sid).driver_exit_code == 3

    sid2 = client.submit_job(
        entrypoint=f"{sys.executable} -c 'import time; time.sleep(600)'",
    )
    _wait_for(
        lambda: client.get_job_status(sid2) == JobStatus.RUNNING,
        msg="job to start",
    )
    assert client.stop_job(sid2)
    _wait_for(
        lambda: client.get_job_status(sid2) == JobStatus.STOPPED,
        msg="job to stop",
    )


def test_job_cli_list(ray_start_regular, capsys):
    from ray_tpu.job import JobSubmissionClient
    from ray_tpu.scripts.cli import main

    client = JobSubmissionClient()
    sid = client.submit_job(entrypoint="true")
    client.wait_until_finished(sid, timeout=60)
    assert main(["job", "list"]) == 0
    out = capsys.readouterr().out
    assert sid in out
    assert main(["job", "status", sid]) == 0


def test_usage_report(ray_start_regular, monkeypatch):
    monkeypatch.setenv("RAY_TPU_usage_stats_enabled", "true")
    from ray_tpu.core.config import GlobalConfig

    GlobalConfig.reload()  # knob values are cached; pick up the env change
    from ray_tpu.core.usage import record_library_usage, usage_report

    record_library_usage("train")
    record_library_usage("train")
    record_library_usage("serve")
    report = usage_report()
    assert report["lib:train"]["count"] == 2
    assert report["lib:serve"]["count"] == 1
