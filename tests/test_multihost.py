"""Multi-host anti-entropy (SURVEY.md §6.8): two local CPU processes
join via jax.distributed.initialize, build a global (replica × element)
mesh with replica spanning processes (the DCN-facing axis), and run the
same mesh_fold program SPMD — the cross-process lattice-join all-reduce
must be bit-identical to a single-device fold."""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "multihost_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_two_process_mesh_fold_bit_identical():
    port = _free_port()
    env = dict(os.environ)
    # The workers set their own XLA flags / platform pins; scrub any
    # inherited device-count forcing so each worker gets exactly 4.
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(port), str(pid)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multihost workers timed out:\n" + "\n---\n".join(outs))
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert f"MULTIHOST_OK process={pid}" in out, out
        assert f"MULTIHOST_SPARSE_OK process={pid}" in out, out


def test_two_process_list_sync():
    """Multi-host List: divergent per-process
    edit logs converge after op-log sync — identifier minting is local,
    identifier PATHS ship over the 2-process runtime, and every process
    reads the same sequence."""
    worker = os.path.join(os.path.dirname(__file__), "multihost_list_worker.py")
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(port), str(pid)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("list workers timed out:\n" + "\n---\n".join(outs))
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert f"MULTIHOST_LIST_OK process={pid}" in out, out
