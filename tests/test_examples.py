"""The examples/ scripts must actually run (on the fake CPU mesh)."""

import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EXAMPLES = sorted(
    f for f in os.listdir(os.path.join(_ROOT, "examples"))
    # workflow_rehearsal runs TWO sequential training legs (preempt ->
    # resume) — too long for this test's shared concurrent deadline; it
    # gets its own sequential test below.
    if f.endswith(".py") and f != "workflow_rehearsal.py"
)


def test_examples_run(tmp_path):
    """All examples, launched CONCURRENTLY (each is import+compile bound;
    running them in parallel takes the wall-clock of the slowest one)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # Scripts with a full-scale default (real_data_convergence) run tiny.
    env["PDDL_EXAMPLE_SMOKE"] = "1"
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # Children write to FILES, not pipes: a pipe drained sequentially
    # would stall any child emitting more than the OS buffer while an
    # earlier sibling is being waited on.
    procs = {}
    logs = {}
    for script in _EXAMPLES:
        logs[script] = open(tmp_path / f"{script}.log", "w+")
        # Isolate mutable state per test run: these examples default to a
        # fixed /tmp work dir shared across sessions.
        extra = (["--work-dir", str(tmp_path / f"work_{script}")]
                 if script in ("real_data_convergence.py",
                               "generate_python.py") else [])
        procs[script] = subprocess.Popen(
            [sys.executable, os.path.join(_ROOT, "examples", script), *extra],
            env=env, cwd=_ROOT, stdout=logs[script],
            stderr=subprocess.STDOUT, text=True,
        )
    failures = []
    deadline = time.monotonic() + 540  # shared: children run concurrently
    try:
        for script, p in procs.items():
            timed_out = False
            try:
                p.wait(timeout=max(1.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                timed_out = True
                p.kill()
                p.wait()
            logs[script].seek(0)
            out = logs[script].read()
            if timed_out:
                failures.append(f"{script} timed out:\n{out[-3000:]}")
            elif p.returncode != 0:
                failures.append(f"{script} (rc={p.returncode}):\n{out[-3000:]}")
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()
        for f in logs.values():
            f.close()
    assert not failures, "\n\n".join(failures)


def test_workflow_rehearsal_smoke(tmp_path):
    """The four-leg reference-workflow rehearsal (preempt -> resume ->
    export -> re-import check) in smoke mode, run ALONE: two sequential
    training legs don't fit the concurrent test's shared deadline."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PDDL_EXAMPLE_SMOKE"] = "1"
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "examples",
                                      "workflow_rehearsal.py"),
         "--work-dir", str(tmp_path / "work"),
         "--artifacts-dir", str(tmp_path / "art")],
        env=env, cwd=_ROOT, capture_output=True, text=True, timeout=540,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert "REHEARSAL PASS" in proc.stdout
