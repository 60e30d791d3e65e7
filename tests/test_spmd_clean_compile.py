"""The sharded train step must compile without SPMD pathologies.

Regression test for the round-1 finding: a vocab-sharded embedding table
under the token gather forced XLA SPMD into "Involuntary full
rematerialization" (replicate-then-repartition of the whole table every
step), destroying multi-chip scaling.  Runs ``dryrun_multichip(8)`` in a
subprocess (XLA logs its SPMD diagnostics to stderr at compile time) and
asserts the diagnostic never appears.

Reference analog: ray has no SPMD compiler, but its release suite gates on
scheduler warnings the same way (release/benchmarks/ — BASELINE.md).
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_dryrun(n_devices: int) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "").replace(
            "--xla_force_host_platform_device_count=8", ""
        )
        + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["N_DEVICES"] = str(n_devices)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py")],
        capture_output=True,
        text=True,
        env=env,
        cwd=REPO,
        timeout=600,
    )


def test_dryrun_8dev_no_involuntary_rematerialization():
    proc = _run_dryrun(8)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "dryrun_multichip(8)" in proc.stdout
    combined = proc.stdout + proc.stderr
    assert "Involuntary full rematerialization" not in combined, (
        "XLA SPMD replicated a sharded tensor wholesale:\n" + combined[-4000:]
    )
