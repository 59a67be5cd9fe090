"""tools/launch.py spawning multi-process kvstore workers
(ref: tools/launch.py:64 +
tests/nightly/dist_sync_kvstore.py run as local processes)."""
import os
import subprocess
import sys


def test_launch_two_process_kvstore():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    # let the children pick their own backend (the worker script pins
    # cpu in-process); drop the 8-device flag so each worker is 1 dev
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "--", sys.executable,
         os.path.join(repo, "tests", "dist_worker_check.py")],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=repo)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-3000:]
    assert "DIST_OK rank 0" in out, out[-3000:]
    assert "DIST_OK rank 1" in out, out[-3000:]


def test_launch_tears_down_on_worker_crash():
    """A crashing worker must fail the job quickly instead of leaving
    peers blocked in a collective (round-3 review regression)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "--", sys.executable, "-c",
         "import os,sys,time\n"
         "if os.environ['MXTPU_WORKER_RANK']=='1': sys.exit(3)\n"
         "time.sleep(600)"],
        capture_output=True, text=True, timeout=60, env=env, cwd=repo)
    assert r.returncode == 3, (r.returncode, r.stderr[-500:])


def test_launch_elastic_restart(tmp_path):
    """--max-restarts relaunches the whole job after a failure; the
    second attempt (simulating resume-from-checkpoint) succeeds."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    marker = tmp_path / "crashed_once"
    script = (
        "import os, sys\n"
        f"m = {str(marker)!r}\n"
        "if os.environ['MXTPU_WORKER_RANK'] == '0' "
        "and not os.path.exists(m):\n"
        "    open(m, 'w').close()\n"
        "    sys.exit(9)\n"
        # one os.write syscall: atomic for <PIPE_BUF, so concurrent
        # workers sharing the pipe can't interleave mid-line
        "os.write(1, ('ATTEMPT %s rank %s\\n' % ("
        "os.environ['MXTPU_RESTART_ATTEMPT'],"
        " os.environ['MXTPU_WORKER_RANK'])).encode())\n")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "--max-restarts", "2", "--",
         sys.executable, "-c", script],
        capture_output=True, text=True, timeout=120, env=env, cwd=repo)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-2000:]
    assert "restarting job (attempt 1/2)" in out, out[-2000:]
    assert "ATTEMPT 1 rank 0" in out, out[-2000:]

    # without restarts the same failure fails the job
    os.unlink(marker)
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "--", sys.executable, "-c", script],
        capture_output=True, text=True, timeout=120, env=env, cwd=repo)
    assert r.returncode == 9


def test_elastic_resume_from_checkpoint(tmp_path):
    """The full elasticity claim (SURVEY §5): kill rank 1 mid-train,
    --max-restarts relaunches the job, workers resume from the last
    checkpoint (not epoch 0) and converge."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env["MXTPU_ELASTIC_DIR"] = str(tmp_path)
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "--max-restarts", "2", "--", sys.executable,
         os.path.join(repo, "tests", "dist_elastic_worker.py")],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=repo)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-3000:]
    assert "CRASHING rank 1 after epoch 1" in out, out[-3000:]
    assert "restarting job (attempt 1/2)" in out, out[-3000:]
    # resumed from the epoch-2 checkpoint, not from scratch
    assert "RESUMED_FROM 2 rank 0" in out, out[-3000:]
    assert "RESUMED_FROM 2 rank 1" in out, out[-3000:]
    assert "ELASTIC_OK rank 0 attempt 1" in out, out[-3000:]
    assert "ELASTIC_OK rank 1 attempt 1" in out, out[-3000:]


def test_launch_elastic_shrink_grow_policy(tmp_path):
    """--elastic restart ledger: a crash shrinks the next world to
    the survivors, an elastic exit (14) re-admits replaced workers
    back to the target world, each with its own counter/log line and
    a fresh MXTPU_WORLD_GENERATION (no jax involved)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = (
        "import os, sys\n"
        "gen = os.environ['MXTPU_WORLD_GENERATION']\n"
        "n = os.environ['MXTPU_NUM_WORKERS']\n"
        "r = os.environ['MXTPU_WORKER_RANK']\n"
        "el = os.environ.get('MXTPU_ELASTIC')\n"
        "os.write(1, f'GEN {gen} WORLD {n} RANK {r} "
        "ELASTIC {el}\\n'.encode())\n"
        "if gen == '1' and r == '1':\n"
        "    sys.exit(5)\n"          # crash -> shrink 2 -> 1
        "if gen == '2':\n"
        "    sys.exit(14)\n")        # coordinated -> grow back to 2
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "--elastic", "--max-elastic-restarts", "3",
         "--", sys.executable, "-c", script],
        capture_output=True, text=True, timeout=120, cwd=repo)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-2000:]
    assert "ELASTIC restart 1/3: world 2 -> 1 (shrink: rank(s) [1]" \
        in out, out[-2000:]
    assert "ELASTIC restart 2/3: world 1 -> 2 (grow" in out, \
        out[-2000:]
    assert "GEN 2 WORLD 1 RANK 0 ELASTIC 1" in out, out[-2000:]
    assert "GEN 3 WORLD 2 RANK 1 ELASTIC 1" in out, out[-2000:]


def test_launch_elastic_budget_and_divergence_split(tmp_path):
    """Divergence (exit 13) keeps consuming --max-restarts even
    under --elastic; the elastic budget refuses past its own cap."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # divergence: --max-restarts 0 -> no restart, rc 13, no ELASTIC
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "1", "--elastic", "--", sys.executable, "-c",
         "import sys; sys.exit(13)"],
        capture_output=True, text=True, timeout=60, cwd=repo)
    assert r.returncode == 13
    assert "ELASTIC restart" not in r.stdout + r.stderr
    # crash loop: budget 1 -> exactly one elastic restart, then out
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "--elastic", "--max-elastic-restarts", "1",
         "--", sys.executable, "-c", "import sys; sys.exit(3)"],
        capture_output=True, text=True, timeout=60, cwd=repo)
    out = r.stdout + r.stderr
    assert r.returncode == 3
    assert "ELASTIC restart 1/1" in out, out[-1500:]
    assert "elastic restart budget spent" in out, out[-1500:]


def test_launch_elastic_ssh_excludes_failed_host(tmp_path):
    """ssh-mode shrink must drop the failed rank's HOST from the
    next assignment (its machine may be gone) and re-derive the
    coordinator from the live pool — not respawn onto the dead box
    with a pinned coordinator."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shim = _write_shim(tmp_path)
    hostfile = tmp_path / "hosts"
    hostfile.write_text("hostA 1\nhostB 1\n")
    log = tmp_path / "shim.log"
    script = (
        "import os, sys\n"
        "if os.environ['MXTPU_WORLD_GENERATION'] == '1' "
        "and os.environ['MXTPU_WORKER_RANK'] == '1':\n"
        "    sys.exit(5)\n")
    env = dict(os.environ)
    env["SSH_SHIM_LOG"] = str(log)
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "--launcher", "ssh", "-H", str(hostfile),
         "--ssh-cmd", shim, "--elastic", "--max-elastic-restarts",
         "2", "--", sys.executable, "-c", script],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=repo)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-2000:]
    assert "excluding failed host(s) ['hostB']" in out, out[-2000:]
    assert "ELASTIC restart 1/2: world 2 -> 1 (shrink" in out, \
        out[-2000:]
    calls = [ln for ln in log.read_text().splitlines()
             if ln.startswith("SHIM ")]
    # attempt 1: both hosts; attempt 2: only hostA (world 1)
    assert len(calls) == 3, calls
    assert calls[2].startswith("SHIM hostA "), calls[2]
    assert "MXTPU_COORD_ADDR=hostA:" in calls[2], calls[2]


def test_elastic_shrink_grow_reshard_e2e(tmp_path):
    """The full elastic claim (docs/elastic.md): elastic:rank0 kill
    mid-step -> launch.py --elastic shrinks the world, the survivor
    resumes from the newest sharded manifest generation RESHARDED
    onto a smaller mesh with the data cursors resharded 2 -> 1
    workers, requests re-admission at a checkpoint boundary (exit
    14), and the grown world finishes the run — zero orphan tmp
    files in the checkpoint directory."""
    from test_data_service import _make_jpeg_rec

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rec = _make_jpeg_rec(str(tmp_path / "ds"), 48, edge=32)
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "--elastic", "--max-elastic-restarts", "3",
         "--env", "MXTPU_FAULT_SPEC=elastic:rank0:5:kill",
         "--env", f"MXTPU_ELASTIC_DIR={tmp_path}",
         "--env", f"MXTPU_ELASTIC_REC={rec}",
         "--", sys.executable,
         os.path.join(repo, "tests", "dist_elastic_reshard_worker.py")],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=repo)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-4000:]
    # gen 1: fresh start on the 8-device mesh, killed mid-step 5
    assert "BOOT gen=1 world=2 devices=8 resumed=None" in out, \
        out[-4000:]
    assert "MXTPU_KILLED injected elastic:rank0 kill" in out, \
        out[-4000:]
    assert "ELASTIC restart 1/3: world 2 -> 1 (shrink: rank(s) [0]" \
        in out, out[-4000:]
    # gen 2: shrunk world resumes the manifest on 4 devices, data
    # cursors resharded 2 -> 1, then requests re-admission
    assert "BOOT gen=2 world=1 devices=4 resumed=4" in out, \
        out[-4000:]
    assert "DATA 2->1" in out, out[-4000:]
    assert "GROW_REQUEST" in out, out[-4000:]
    assert "ELASTIC restart 2/3: world 1 -> 2 (grow" in out, \
        out[-4000:]
    # gen 3: grown world resumes at the grow checkpoint and finishes
    assert "BOOT gen=3 world=2 devices=8 resumed=8" in out, \
        out[-4000:]
    assert "DATA 1->1" in out, out[-4000:]
    assert "ELASTIC_DONE gen=3 steps=12" in out, out[-4000:]
    # zero half-written tmp files anywhere near the checkpoints
    orphans = [f for _, _, fs in os.walk(tmp_path) for f in fs
               if ".tmp." in f]
    assert orphans == [], orphans


SSH_SHIM = """#!/bin/sh
# Faithful stand-in for ssh in an image without an ssh client: accepts
# `shim [-o opt]... host 'remote command'` and runs the command through
# a local shell, exactly as sshd would hand it to the remote login
# shell.  Records each call so the test can assert per-host dispatch.
echo "SHIM $@" >> "$SSH_SHIM_LOG"
while [ $# -gt 0 ]; do
    case "$1" in
        -o) shift 2 ;;
        -*) shift ;;
        *) break ;;
    esac
done
host="$1"; shift
exec sh -c "$*"
"""


def _write_shim(tmp_path):
    shim = tmp_path / "fake_ssh"
    shim.write_text(SSH_SHIM)
    shim.chmod(0o755)
    return str(shim)


def test_launch_ssh_two_host_kvstore(tmp_path):
    """--launcher ssh spawns real per-host remote-shell sessions with
    env propagated inline.  The transport is
    swapped for a local shim (this image has no ssh client); with a
    real ssh binary the identical code path runs unchanged."""
    import socket

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shim = _write_shim(tmp_path)
    hostfile = tmp_path / "hosts"
    hostfile.write_text("# two slots on this machine\nlocalhost 2\n")
    log = tmp_path / "shim.log"

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["SSH_SHIM_LOG"] = str(log)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "--launcher", "ssh", "-H", str(hostfile),
         "--port", str(port), "--ssh-cmd", shim,
         "--env", "MXTPU_TEST_FLAG=hello", "--", sys.executable,
         os.path.join(repo, "tests", "dist_worker_check.py")],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=repo)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-3000:]
    assert "DIST_OK rank 0" in out, out[-3000:]
    assert "DIST_OK rank 1" in out, out[-3000:]
    # the transport was exercised once per worker, to the right host
    calls = log.read_text().strip().splitlines()
    assert len(calls) == 2, calls
    assert all("localhost" in c for c in calls), calls
    # inline env propagation (rank + custom --env var)
    joined = "\n".join(calls)
    assert "MXTPU_WORKER_RANK=0" in joined, joined
    assert "MXTPU_WORKER_RANK=1" in joined, joined
    assert "MXTPU_TEST_FLAG=hello" in joined, joined
    assert f"MXTPU_COORD_ADDR=localhost:{port}" in joined, joined


def test_launch_ssh_hostfile_round_robin(tmp_path):
    """Ranks fill each host's slots before wrapping; rank 0's host is
    the coordinator."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import launch as launch_mod
    hosts = [("a", 2), ("b", 1)]
    assert launch_mod._assign_hosts(hosts, 5) == \
        ["a", "a", "b", "a", "a"]
    hf = tmp_path / "hosts"
    hf.write_text("h1 1\n# comment\n\nh2 3\n")
    assert launch_mod._parse_hostfile(str(hf)) == [("h1", 1),
                                                   ("h2", 3)]


def test_hostfile_zero_slots_is_clean_error(tmp_path):
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import launch as launch_mod
    import pytest
    with pytest.raises(ValueError, match="no usable slots"):
        launch_mod._assign_hosts([("drained-host", 0)], 2)


def test_dist_rank_from_mpi_env(monkeypatch):
    """--launcher mpi workers get their rank from the MPI runtime's
    env (dist._env_rank), not MXTPU_WORKER_RANK."""
    from incubator_mxnet_tpu import dist
    monkeypatch.setenv("MXTPU_RANK_FROM_MPI", "1")
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "3")
    monkeypatch.setenv("MXTPU_WORKER_RANK", "0")   # must be ignored
    assert dist._env_rank() == 3
    monkeypatch.delenv("OMPI_COMM_WORLD_RANK")
    monkeypatch.setenv("SLURM_PROCID", "5")
    assert dist._env_rank() == 5
    monkeypatch.delenv("SLURM_PROCID")
    import pytest
    with pytest.raises(RuntimeError, match="mpirun"):
        dist._env_rank()
    monkeypatch.delenv("MXTPU_RANK_FROM_MPI")
    monkeypatch.setenv("MXTPU_WORKER_RANK", "2")
    assert dist._env_rank() == 2


def test_kill_job_cleans_up_stuck_workers(tmp_path):
    """tools/kill_job.py (the reference kill-mxnet.py role) walks the
    hostfile over the launch transport and kills matching processes."""
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shim = _write_shim(tmp_path)
    hostfile = tmp_path / "hosts"
    hostfile.write_text("localhost 1\n")
    log = tmp_path / "shim.log"

    tag = f"mxtpu_stuck_{os.getpid()}"
    stuck = subprocess.Popen(
        [sys.executable, "-c",
         f"import time  # {tag}\ntime.sleep(600)"])
    try:
        time.sleep(0.3)
        assert stuck.poll() is None
        env = dict(os.environ)
        env["SSH_SHIM_LOG"] = str(log)
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "tools",
                                          "kill_job.py"),
             "-H", str(hostfile), "--ssh-cmd", shim, tag],
            capture_output=True, text=True, timeout=60, env=env)
        assert r.returncode == 0, r.stdout + r.stderr
        deadline = time.time() + 10
        while stuck.poll() is None and time.time() < deadline:
            time.sleep(0.1)
        assert stuck.poll() is not None, "stuck worker survived"
    finally:
        if stuck.poll() is None:
            stuck.kill()

    # refuses self-matching patterns
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "kill_job.py"),
         "launch.py"],
        capture_output=True, text=True, timeout=30)
    assert r.returncode != 0
