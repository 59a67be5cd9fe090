#!/usr/bin/env python
"""Distributed job launcher (ref: tools/launch.py:64-83 — the dmlc
tracker's ssh/local submission modes).

Spawns N worker processes for data-parallel training.  Where the
reference wires ps-lite (scheduler + servers + workers over DMLC_*
env vars), this launcher wires the JAX distributed runtime: every
worker gets the coordinator address of rank 0 and joins via
`incubator_mxnet_tpu.dist.init()` (called automatically by
`kvstore.create('dist_sync')`).

Usage:
    # N processes on this host
    python tools/launch.py -n 2 python train.py --kv-store dist_sync

    # N processes across the hosts in a hostfile, over ssh
    python tools/launch.py -n 8 -H hosts --launcher ssh \
        python train.py --kv-store dist_sync

    # serving fleet: router + 3 replicas (docs/serving.md "Fleet");
    # serve.py switches on MXTPU_FLEET_ROLE
    python tools/launch.py --serve-fleet 3 --max-restarts 2 \
        python serve.py

Launch modes:
    local (default) — N processes on this host (the reference's
        `--launcher local` used by tests/nightly/dist_sync_kvstore.py)
    ssh — one ssh session per worker, ranks assigned round-robin over
        the hostfile (lines: "host [slots]"); rank 0's host serves as
        the coordinator on --port.  Env is propagated inline in the
        remote command (MXTPU_*, PYTHONPATH, plus any --env KEY=VAL),
        like the reference's tracker exports DMLC_* over ssh
        (ref: dmlc_tracker/ssh.py role).  --ssh-cmd substitutes the
        transport (tests use a local shim; GCE TPU pods use
        `gcloud compute tpus tpu-vm ssh` — see README).
    mpi — exec mpirun with -x env forwarding when mpirun exists.
    sge/yarn — print the per-host commands (documented de-scope:
        those schedulers' submission APIs are site-specific).

`-s` (server count) is accepted for CLI parity and ignored: there are
no parameter servers in the collective design.
"""
import argparse
import importlib.util
import json
import os
import shlex
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time


# resilience.DivergedError.EXIT_CODE, mirrored by value: the
# launcher deliberately never imports the package (it must run
# before jax is installed/importable on a fresh host)
DIVERGED_EXIT = 13
# resilience.ELASTIC_EXIT_CODE, mirrored by value: a worker exits
# with this after a coordinated elastic abort (peer died inside a
# collective) or a deliberate restart request (re-admission at a
# checkpoint boundary) — with --elastic the restart ledger counts it
# separately from crashes and divergence
ELASTIC_EXIT = 14
# resilience.OOM_EXIT_CODE, mirrored by value: a worker exits with
# this after device memory exhaustion survived neither the preflight
# degrade ladder nor the one-rung runtime retry (docs/memory.md) —
# deterministic, so restarts are NOT elastic events and rarely help
# unless capacity or batch size changed
OOM_EXIT = 15


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _parse_hostfile(path):
    """Lines of "host" or "host slots"; '#' comments allowed."""
    hosts = []
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            parts = line.split()
            hosts.append((parts[0],
                          int(parts[1]) if len(parts) > 1 else 1))
    if not hosts:
        raise ValueError(f"hostfile {path} lists no hosts")
    return hosts


def _assign_hosts(hosts, n):
    """rank -> host, filling each host's slots before wrapping."""
    pool = [h for h, slots in hosts for _ in range(slots)]
    if not pool:
        raise ValueError("hostfile has no usable slots (every host "
                         "has 'slots' of 0)")
    return [pool[r % len(pool)] for r in range(n)]


def _worker_env(args, rank, coord, attempt, world=None):
    env = {
        "MXTPU_NUM_WORKERS": str(world if world is not None
                                 else args.num_workers),
        "MXTPU_WORKER_RANK": str(rank),
        "MXTPU_COORD_ADDR": coord,
        "MXTPU_RESTART_ATTEMPT": str(attempt),
        # which world a metric/log line came from: generation 1 is
        # the first launch, each restart (crash, divergence, or
        # elastic resize) increments it
        "MXTPU_WORLD_GENERATION": str(attempt + 1),
    }
    if getattr(args, "elastic", False):
        # workers map uncaught CollectiveAbortedError / collective
        # deadline expiry to the distinct elastic exit (14) instead
        # of a crash (resilience.install_diverged_exithook)
        env["MXTPU_ELASTIC"] = "1"
    if getattr(args, "data_timeout", None) is not None:
        # input pipelines must fail before the whole job looks hung:
        # a worker whose data stalls raises DataPipelineError (a
        # clean, restartable exit) while its heartbeat is still
        # beating — heartbeats only catch wedged *processes*
        env["MXTPU_DATA_TIMEOUT"] = str(args.data_timeout)
    if getattr(args, "data_workers", None) is not None:
        # every rank runs its own data service with this many decode
        # worker processes (DataServiceIter reads the flag when
        # num_workers is not passed; docs/data_service.md)
        env["MXTPU_DATA_WORKERS"] = str(args.data_workers)
    if getattr(args, "nonfinite_policy", None):
        env["MXTPU_NONFINITE_POLICY"] = args.nonfinite_policy
    if getattr(args, "max_bad_steps", None) is not None:
        env["MXTPU_MAX_BAD_STEPS"] = str(args.max_bad_steps)
    for kv in args.env:
        if "=" not in kv:
            raise ValueError(f"--env wants KEY=VALUE, got {kv!r}")
        k, v = kv.split("=", 1)
        env[k] = v
    return env


def _ssh_argv(args, host, remote_cmd):
    base = shlex.split(args.ssh_cmd)
    if os.path.basename(base[0]) == "ssh":
        # -tt: force a pty so tearing down the local ssh client HUPs
        # the remote worker's process group — without it, killing ssh
        # leaves the remote python alive, blocked in a collective and
        # holding its TPU chips, and any elastic restart on the same
        # hosts would find the devices taken
        base += ["-tt", "-o", "BatchMode=yes",
                 "-o", "StrictHostKeyChecking=no"]
    return base + [host, remote_cmd]


def _remote_command(args, rank, coord, attempt, cmd, world=None):
    """One POSIX-shell line: cd to the launch cwd, export env inline,
    exec the training command (the reference tracker's export+exec
    pattern over ssh)."""
    env = _worker_env(args, rank, coord, attempt, world)
    if os.environ.get("PYTHONPATH"):
        env.setdefault("PYTHONPATH", os.environ["PYTHONPATH"])
    assigns = " ".join(f"{k}={shlex.quote(v)}"
                       for k, v in sorted(env.items()))
    prog = " ".join(shlex.quote(c) for c in cmd)
    return (f"cd {shlex.quote(os.getcwd())} && "
            f"{assigns} exec {prog}")


def _env_float(name, default):
    """Forgiving env-float read matching the package registry's
    semantics (MXNET_ prefix fallback, bad value -> default) without
    importing the package into the launcher process."""
    for key in (name, "MXNET_" + name[len("MXTPU_"):]):
        raw = os.environ.get(key)
        if raw is not None:
            try:
                return float(raw)
            except ValueError:
                pass
    return default


def _hb_path(hb_dir, attempt, rank):
    """Heartbeat file for one worker of one attempt (fresh file per
    attempt: a restart must not inherit the dead attempt's mtimes)."""
    return os.path.join(hb_dir, f"hb-{attempt}-{rank}")


# ---------------------------------------------------------------------------
# live introspection (docs/observability.md "Introspection plane")
#
# Each worker embeds a debugz endpoint (debugz.maybe_start, port
# published to MXTPU_DEBUGZ_PORTFILE = heartbeat path + ".debugz").
# The monitor prefers asking a live process over reading file mtimes:
# healthz answers prove liveness even when a slow filesystem delays
# the beat, and varz returns a *current* snapshot instead of the last
# interval's.  Every live call is deadline-bounded, and the heartbeat
# file remains the fallback — a job with MXTPU_DEBUGZ=0 (or an old
# worker) is monitored exactly as before.
# ---------------------------------------------------------------------------

_DZ_CLIENT = {"loaded": False, "mod": None}


def _dz_portfile(hb_path):
    """Debugz port file for one worker, derived from its heartbeat
    path (same per-attempt freshness)."""
    return hb_path + ".debugz"


def _dz_client():
    """Lazy-load the stdlib frame client from the adjacent
    tools/debugz.py (the launcher never imports the package); None
    when unavailable — all callers fall back to heartbeat files."""
    if not _DZ_CLIENT["loaded"]:
        _DZ_CLIENT["loaded"] = True
        try:
            path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "debugz.py")
            spec = importlib.util.spec_from_file_location(
                "_launch_debugz_client", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _DZ_CLIENT["mod"] = mod
        except Exception:
            _DZ_CLIENT["mod"] = None
    return _DZ_CLIENT["mod"]


def _dz_call(hb_path, msg, deadline):
    """One bounded debugz call to the worker owning ``hb_path``;
    None on any failure (no endpoint, hung peer, torn port file)."""
    dz = _dz_client()
    if dz is None or hb_path is None:
        return None
    try:
        with open(_dz_portfile(hb_path)) as f:
            host, port = f.read().strip().rsplit(":", 1)
        return dz.frame_call(host, int(port), msg, timeout=deadline)
    except Exception:
        return None


def _live_fresh(hb_path, deadline=1.0):
    """True when the worker's debugz healthz answers — direct proof
    of liveness, used before trusting a stale file mtime (a loaded
    NFS heartbeat dir must not get a healthy rank killed)."""
    reply = _dz_call(hb_path, {"op": "healthz"}, deadline)
    return reply is not None and "error" not in reply


def _live_snapshots(hb_files, deadline=1.0):
    """rank -> current telemetry snapshot via live debugz ``varz``,
    queried concurrently with one bounded thread per rank (a
    SIGSTOPped rank costs ~``deadline`` seconds total, not per
    rank).  Ranks without a live reply are simply absent."""
    if not hb_files or _dz_client() is None:
        return {}
    out = {}
    lock = threading.Lock()

    def one(rank, path):
        reply = _dz_call(path, {"op": "varz"}, deadline)
        snap = reply.get("telemetry") if reply else None
        if isinstance(snap, dict):
            with lock:
                out[rank] = snap

    threads = [threading.Thread(target=one, args=(r, p), daemon=True)
               for r, p in hb_files.items()]
    for t in threads:
        t.start()
    join_by = time.time() + deadline + 0.5
    for t in threads:
        t.join(max(join_by - time.time(), 0.001))
    with lock:
        return dict(out)


# ---------------------------------------------------------------------------
# telemetry aggregation (docs/observability.md)
#
# Workers append their current metric snapshot as a second JSON line
# of the heartbeat file (resilience._beat + telemetry.heartbeat_payload),
# so the launcher can aggregate ranks over the channel it already
# monitors — no extra socket, no extra files.
# ---------------------------------------------------------------------------

# counters worth surfacing in the one-line status (error/recovery
# signals an operator watches a hung or degrading job for)
_ERROR_COUNTERS = ("retry_attempts_total", "collective_aborts_total",
                   "data_quarantined_records_total",
                   "dataloader_worker_restarts_total",
                   "data_service_worker_restarts_total",
                   "data_service_net_restarts_total",
                   "sentinel_bad_steps_total",
                   "sentinel_skipped_steps_total",
                   "sentinel_divergences_total", "rollbacks_total",
                   "checkpoint_fallbacks_total",
                   "loss_scale_backoffs_total",
                   # serving SLO/survival signals (docs/serving.md):
                   # load shed at the door, deadlines blown, clients
                   # gone, engines draining for shutdown
                   "serving_rejected_total", "serving_expired_total",
                   "serving_cancelled_total", "serving_drains_total",
                   # memory-pressure survival (docs/memory.md):
                   # preflight ladder rungs taken, runtime OOM retries
                   "memory_plan_degrades_total", "oom_retries_total",
                   # anomaly watchdog episodes (docs/observability.md
                   # "Introspection plane")
                   "anomaly_detections_total")


def _read_heartbeat(path):
    """Parse one worker heartbeat file -> (beat_ts, snapshot|None).

    Line 1 is the bare timestamp (unchanged contract: mtime monitors
    and old parsers keep working); the last line, when it is a JSON
    object, is the worker's telemetry snapshot.  Any malformed
    content degrades to (None, None)/partial — the monitor must never
    crash on a torn read."""
    try:
        with open(path) as f:
            lines = f.read().splitlines()
    except OSError:
        return None, None
    ts = None
    if lines:
        try:
            ts = float(lines[0])
        except ValueError:
            pass
    snap = None
    if len(lines) > 1 and lines[-1].lstrip().startswith("{"):
        try:
            snap = json.loads(lines[-1])
        except ValueError:
            pass
    return ts, snap


def _collect_snapshots(hb_files):
    """rank -> snapshot, live debugz ``varz`` preferred (current
    counters — straggler step counts from *now*, not the last beat),
    heartbeat-file ride-along as the per-rank fallback."""
    snaps = _live_snapshots(hb_files)
    for rank, path in (hb_files or {}).items():
        if rank in snaps:
            continue
        _, snap = _read_heartbeat(path)
        if snap is not None:
            snaps[rank] = snap
    return snaps


def _rank_memory(snap):
    """One rank's memory footprint in bytes from its snapshot gauges:
    device live bytes when the backend reports them, host RSS
    otherwise (CPU-only workers still show their real footprint)."""
    gauges = snap.get("gauges") or {}
    dev = gauges.get("device_live_bytes", 0.0) or 0.0
    return dev if dev > 0 else (gauges.get("host_rss_bytes", 0.0)
                                or 0.0)


def _fmt_bytes(n):
    if n >= 1 << 30:
        return f"{n / (1 << 30):.1f}GB"
    if n >= 1 << 20:
        return f"{n / (1 << 20):.0f}MB"
    return f"{n / (1 << 10):.0f}KB"


def _aggregate_telemetry(snaps):
    """Combine per-rank snapshots: counters sum across ranks,
    throughput sums, per-rank step counts identify the straggler
    (the rank whose step counter trails the fleet), per-rank memory
    (device live bytes, falling back to host RSS) identifies the
    max-memory rank — the one that OOMs first."""
    agg = {"ranks": sorted(snaps), "counters": {}, "throughput": 0.0,
           "steps": {}, "straggler": None, "memory": {},
           "compiles": {}, "max_memory": None, "data_img_s": 0.0,
           "data_img_s_by_rank": {}, "serve_queue": 0,
           "serve_queued_tokens": 0,
           "plan_delta": {}, "plan_delta_worst": None}
    for rank, snap in snaps.items():
        for name, v in (snap.get("counters") or {}).items():
            agg["counters"][name] = agg["counters"].get(name, 0) + v
        gauges = snap.get("gauges") or {}
        agg["throughput"] += gauges.get("throughput_samples_per_sec",
                                        0.0)
        ds = gauges.get("data_service_img_per_sec", 0.0) or 0.0
        if ds > 0:
            agg["data_img_s"] += ds
            agg["data_img_s_by_rank"][rank] = ds
        # serving admission pressure (docs/serving.md): queue depth
        # and queued prompt tokens summed over this host's engines
        agg["serve_queue"] += int(
            gauges.get("serving_queue_depth", 0) or 0)
        agg["serve_queued_tokens"] += int(
            gauges.get("serving_queued_prompt_tokens", 0) or 0)
        agg["steps"][rank] = (snap.get("counters") or {}).get(
            "train_steps_total", 0)
        mem = _rank_memory(snap)
        if mem > 0:
            agg["memory"][rank] = mem
        # memory planner drift (docs/memory.md): predicted minus
        # measured live bytes, shipped per-beat by the tracing layer
        delta = gauges.get("memory_plan_delta_bytes")
        if delta is not None:
            agg["plan_delta"][rank] = float(delta)
        compiles = (snap.get("counters") or {}).get(
            "compile_events_total", 0)
        if compiles:
            agg["compiles"][rank] = compiles
    if len(agg["steps"]) > 1:
        lo = min(agg["steps"], key=agg["steps"].get)
        hi = max(agg["steps"].values())
        if agg["steps"][lo] < hi:
            agg["straggler"] = (lo, agg["steps"][lo], hi)
    if agg["memory"]:
        hi_rank = max(agg["memory"], key=agg["memory"].get)
        agg["max_memory"] = (hi_rank, agg["memory"][hi_rank])
    if agg["plan_delta"]:
        worst = max(agg["plan_delta"],
                    key=lambda r: abs(agg["plan_delta"][r]))
        agg["plan_delta_worst"] = (worst, agg["plan_delta"][worst])
    return agg


def _format_status(agg):
    """One cluster status line from an aggregate."""
    steps = sum(agg["steps"].values())
    parts = [f"{len(agg['ranks'])} rank(s)", f"steps={steps}"]
    if agg["throughput"] > 0:
        parts.append(f"{agg['throughput']:.1f} samples/s")
    if agg.get("data_img_s", 0) > 0:
        parts.append(f"data: {agg['data_img_s']:.0f} img/s")
    if agg.get("data_fleet") is not None:
        img_s, restarts, healthy, total = agg["data_fleet"]
        part = f"remote data: {healthy}/{total} host(s)"
        if img_s > 0:
            part += f" {img_s:.0f} img/s"
        if restarts:
            part += f" restarts={restarts}"
        parts.append(part)
    if agg.get("serve_queue", 0) > 0:
        parts.append(f"serve queue: {agg['serve_queue']} req "
                     f"({agg['serve_queued_tokens']} tok)")
    errs = [f"{n}={agg['counters'][n]}" for n in _ERROR_COUNTERS
            if agg["counters"].get(n)]
    if errs:
        parts.append("errors: " + " ".join(errs))
    if agg["straggler"] is not None:
        rank, at, hi = agg["straggler"]
        parts.append(f"straggler: rank {rank} at step {at}/{hi}")
    if agg.get("max_memory") is not None:
        rank, mem = agg["max_memory"]
        part = f"mem: max rank {rank} at {_fmt_bytes(mem)}"
        if agg.get("plan_delta_worst") is not None:
            drank, delta = agg["plan_delta_worst"]
            sign = "+" if delta >= 0 else "-"
            part += (f" (plan {sign}{_fmt_bytes(abs(delta))} "
                     f"rank {drank})")
        parts.append(part)
    if agg.get("compiles"):
        parts.append(
            f"compiles={sum(agg['compiles'].values())}")
    return "launch.py: status: " + " | ".join(parts)


def _format_report(snaps):
    """Final multi-line run report from the last snapshots."""
    if not snaps:
        return ("launch.py: run report: no worker telemetry "
                "(MXTPU_TELEMETRY=0, or the workers never joined "
                "dist.init)")
    agg = _aggregate_telemetry(snaps)
    lines = ["launch.py: ----- run report -----"]
    for rank in agg["ranks"]:
        gauges = snaps[rank].get("gauges") or {}
        tp = gauges.get("throughput_samples_per_sec")
        mem = agg["memory"].get(rank)
        compiles = agg["compiles"].get(rank)
        data_tp = agg["data_img_s_by_rank"].get(rank)
        lines.append(
            f"launch.py:   rank {rank}: steps="
            f"{agg['steps'].get(rank, 0)}"
            + (f" {tp:.1f} samples/s" if tp else "")
            + (f" data={data_tp:.0f} img/s" if data_tp else "")
            + (f" mem={_fmt_bytes(mem)}" if mem else "")
            + (f" compiles={compiles}" if compiles else ""))
    nonzero = {n: v for n, v in sorted(agg["counters"].items()) if v}
    if nonzero:
        lines.append("launch.py:   counters (summed over ranks):")
        for name, v in nonzero.items():
            lines.append(f"launch.py:     {name} = {v}")
    if agg["straggler"] is not None:
        rank, at, hi = agg["straggler"]
        lines.append(f"launch.py:   straggler: rank {rank} finished "
                     f"at step {at} of {hi}")
    if agg.get("max_memory") is not None:
        rank, mem = agg["max_memory"]
        lines.append(f"launch.py:   max memory: rank {rank} at "
                     f"{_fmt_bytes(mem)}")
    if agg.get("plan_delta_worst") is not None:
        rank, delta = agg["plan_delta_worst"]
        sign = "over-predicted by" if delta >= 0 \
            else "UNDER-predicted by"
        lines.append(
            f"launch.py:   memory plan drift: rank {rank} "
            f"{sign} {_fmt_bytes(abs(delta))} (predicted minus "
            "measured live; docs/memory.md)")
    if agg.get("serve_queue", 0) > 0:
        lines.append(
            f"launch.py:   serving queue at exit: "
            f"{agg['serve_queue']} req "
            f"({agg['serve_queued_tokens']} tok) — drained engines "
            "should exit with an empty queue or a snapshot")
    lines.append("launch.py: -----------------------")
    return "\n".join(lines)


def _run_once(spawners, hb_files=None, hb_timeout=0,
              status_interval=0, data_fleet=None):
    """Start every worker; first nonzero exit tears the job down (a
    crashing worker mid-collective leaves peers blocked forever — the
    reference's ps-lite scheduler dies the same way).

    Heartbeat monitoring (hb_files: rank -> path, hb_timeout > 0)
    closes the gap poll() cannot see: a *hung* worker — wedged in a
    dead collective or a C-level deadlock — never exits, so the only
    liveness signal is its heartbeat file going stale.  Such a worker
    is killed, which turns the hang into an ordinary failure the
    --max-restarts loop already handles.  A worker that never created
    its file is not monitored (it may be a pre-dist warmup phase or a
    command that does not call dist.init()).

    With status_interval > 0 the monitor additionally aggregates the
    telemetry snapshots riding the heartbeat files into one periodic
    cluster status line (throughput, stragglers, error counters) —
    the operator's view of *where* a slow job is slow.

    Returns ``(rc, failed_ranks)`` — the ranks observed to fail on
    their own (crash exit or hung-kill), as opposed to peers torn
    down by the job teardown; the --elastic restart policy shrinks
    the next world by exactly these ranks."""
    procs = []
    next_status = time.time() + status_interval \
        if status_interval > 0 and hb_files else None
    failed = set()
    try:
        for spawn in spawners:
            procs.append(spawn())
        rc = 0
        pending = dict(enumerate(procs))
        killed = set()        # ranks already killed as hung: one
                              # SIGKILL + one log line each, then we
                              # just wait for the reap
        while pending and rc == 0:
            now = time.time()
            if data_fleet is not None:
                # data hosts are supervised alongside the training
                # monitor: hung-host kill + respawn-in-place (the
                # training ranks' shards fail over meanwhile)
                data_fleet.poll(now)
            if next_status is not None and now >= next_status:
                next_status = now + status_interval
                snaps = _collect_snapshots(hb_files)
                if snaps or data_fleet is not None:
                    agg = _aggregate_telemetry(snaps)
                    if data_fleet is not None:
                        agg["data_fleet"] = data_fleet.telemetry()
                    print(_format_status(agg), file=sys.stderr)
            for r, p in list(pending.items()):
                code = p.poll()
                if code is None:
                    if hb_timeout > 0 and hb_files and r in hb_files \
                            and r not in killed:
                        try:
                            age = now - os.path.getmtime(hb_files[r])
                        except OSError:
                            continue    # no heartbeat yet: unmonitored
                        if age > hb_timeout \
                                and not _live_fresh(hb_files[r]):
                            # stale file AND no live healthz answer:
                            # truly wedged (a SIGSTOPped worker fails
                            # both; a slow-filesystem one passes the
                            # bounded live probe and survives)
                            print(f"launch.py: worker {r} hung (no "
                                  f"heartbeat for {age:.0f}s > "
                                  f"{hb_timeout:.0f}s, debugz "
                                  "unresponsive); killing it",
                                  file=sys.stderr)
                            p.kill()
                            killed.add(r)
                    continue
                del pending[r]
                if code != 0:
                    print(f"launch.py: worker {r} exited with "
                          f"{code}; terminating the job",
                          file=sys.stderr)
                    failed.add(r)
                    rc = code or 1
            time.sleep(0.05)
        return rc, failed
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 10
        for p in procs:
            while p.poll() is None and time.time() < deadline:
                time.sleep(0.05)
            if p.poll() is None:
                p.kill()


# ---------------------------------------------------------------------------
# serving fleet mode (--serve-fleet, docs/serving.md "Fleet")
#
# One router + N replica workers on this host.  Unlike training
# (collective: one dead rank wedges the world, so restart is
# whole-job), a serving replica is independent — the router
# re-dispatches its in-flight requests to survivors — so a dead or
# hung replica is respawned *in place* while the fleet keeps serving.
# The router process decides the job: exit 0 is success (the replicas
# are then stopped), any other exit tears the fleet down.
# ---------------------------------------------------------------------------

def _fleet_env(args, role, rank, router_port, replica_ports):
    """Env for one fleet member.  The same user command runs as every
    member and switches on MXTPU_FLEET_ROLE (router | replica); the
    wiring rides the other exports — ServingRouter defaults its
    replica list from MXTPU_REPLICA_ADDRS and ReplicaServer its port
    from MXTPU_REPLICA_PORT, so a role-switch script needs no CLI
    plumbing of its own."""
    env = {
        "MXTPU_FLEET_ROLE": role,
        "MXTPU_FLEET_REPLICAS": str(len(replica_ports)),
        "MXTPU_ROUTER_PORT": str(router_port),
        "MXTPU_REPLICA_ADDRS": ",".join(
            f"127.0.0.1:{p}" for p in replica_ports),
        "MXTPU_WORKER_RANK": str(rank),
    }
    if role == "replica":
        env["MXTPU_REPLICA_PORT"] = str(replica_ports[rank])
    for kv in args.env:
        if "=" not in kv:
            raise ValueError(f"--env wants KEY=VALUE, got {kv!r}")
        k, v = kv.split("=", 1)
        env[k] = v
    return env


def _fleet_status(snaps, healthy, n, rate_state):
    """One fleet status line: replica health from process liveness +
    heartbeat freshness, request rate from the delta of the fleet's
    summed serving_requests_total between ticks."""
    total = sum((s.get("counters") or {})
                .get("serving_requests_total", 0)
                for s in snaps.values())
    now = time.time()
    rate = 0.0
    if rate_state["ts"] is not None and now > rate_state["ts"]:
        rate = max(0, total - rate_state["total"]) \
            / (now - rate_state["ts"])
    rate_state["ts"], rate_state["total"] = now, total
    parts = [f"fleet: {healthy}/{n} healthy, {rate:.1f} req/s"]
    agg = _aggregate_telemetry(snaps)
    if agg.get("serve_queue", 0) > 0:
        parts.append(f"serve queue: {agg['serve_queue']} req "
                     f"({agg['serve_queued_tokens']} tok)")
    errs = [f"{nm}={agg['counters'][nm]}" for nm in _ERROR_COUNTERS
            if agg["counters"].get(nm)]
    if errs:
        parts.append("errors: " + " ".join(errs))
    return "launch.py: status: " + " | ".join(parts)


def _run_fleet(args, cmd, hb_dir):
    """--serve-fleet monitor loop: spawn router + N replicas, respawn
    dead/hung replicas in place under the --max-restarts ledger,
    follow the router's exit."""
    n = args.serve_fleet
    router_port = _free_port()
    replica_ports = [_free_port() for _ in range(n)]
    members = {}        # key -> {proc, hb, role, rank, killed}
    gens = {}           # key -> spawn generation (fresh heartbeat
                        # file per respawn: a replacement must not
                        # inherit the dead replica's mtimes)

    def spawn(role, rank):
        key = "router" if role == "router" else f"replica-{rank}"
        gen = gens.get(key, -1) + 1
        gens[key] = gen
        env = dict(os.environ)
        env.update(_fleet_env(args, role, rank, router_port,
                              replica_ports))
        env["MXTPU_RESTART_ATTEMPT"] = str(gen)
        env["MXTPU_WORLD_GENERATION"] = str(gen + 1)
        hb = None
        if hb_dir is not None:
            hb = _hb_path(hb_dir, gen, key)
            env["MXTPU_HEARTBEAT_FILE"] = hb
            env["MXTPU_HEARTBEAT_INTERVAL"] = \
                str(args.heartbeat_interval)
            env["MXTPU_DEBUGZ_PORTFILE"] = _dz_portfile(hb)
        members[key] = {"proc": subprocess.Popen(cmd, env=env),
                        "hb": hb, "role": role, "rank": rank,
                        "killed": False}

    def hb_fresh(m, now):
        """Healthy = alive process + fresh (or not-yet-created)
        heartbeat; a replica mid-dispatch with a stale beat is the
        one the router's breaker is about to open on."""
        if m["proc"].poll() is not None:
            return False
        if args.heartbeat_timeout <= 0 or m["hb"] is None:
            return True
        try:
            age = now - os.path.getmtime(m["hb"])
        except OSError:
            return True     # no heartbeat yet: unmonitored
        return age <= args.heartbeat_timeout \
            or _live_fresh(m["hb"])

    restarts = 0
    rate_state = {"ts": None, "total": 0}
    rc = 1
    try:
        spawn("router", 0)
        for r in range(n):
            spawn("replica", r)
        next_status = time.time() + args.status_interval \
            if args.status_interval > 0 and hb_dir is not None \
            else None
        done = False
        while not done:
            now = time.time()
            # hung-member kill (same heartbeat-staleness rule as
            # training workers): turns a wedged replica into an
            # ordinary dead one the respawn path handles
            for key, m in members.items():
                p = m["proc"]
                if p.poll() is None and args.heartbeat_timeout > 0 \
                        and m["hb"] is not None and not m["killed"]:
                    try:
                        age = now - os.path.getmtime(m["hb"])
                    except OSError:
                        continue    # no heartbeat yet: unmonitored
                    if age > args.heartbeat_timeout \
                            and not _live_fresh(m["hb"]):
                        print(f"launch.py: fleet member {key} hung "
                              f"(no heartbeat for {age:.0f}s > "
                              f"{args.heartbeat_timeout:.0f}s, "
                              "debugz unresponsive); killing it",
                              file=sys.stderr)
                        p.kill()
                        m["killed"] = True
            # the router's exit decides the job
            code = members["router"]["proc"].poll()
            if code is not None:
                if code == 0:
                    print("launch.py: router exited cleanly; "
                          "stopping the replicas", file=sys.stderr)
                    rc = 0
                else:
                    print(f"launch.py: router exited with {code}; "
                          "terminating the fleet", file=sys.stderr)
                    rc = code or 1
                break
            # dead replicas respawn in place under the restart ledger
            for key, m in list(members.items()):
                if m["role"] != "replica" or m.get("reaped"):
                    continue
                code = m["proc"].poll()
                if code is None:
                    continue
                if code == 0 and not m["killed"]:
                    # deliberate exit: a replica that drained (the
                    # router's fleet drain, or its own SIGTERM
                    # snapshot-then-drain) is done, not dead
                    print(f"launch.py: replica {m['rank']} exited "
                          "cleanly (drained); not respawning",
                          file=sys.stderr)
                    m["reaped"] = True
                    continue
                why = "hung (killed)" if m["killed"] \
                    else f"exited with {code}"
                if restarts >= args.max_restarts:
                    print(f"launch.py: replica {m['rank']} {why}; "
                          f"restart budget spent ({restarts}/"
                          f"{args.max_restarts}); terminating the "
                          "fleet", file=sys.stderr)
                    rc = code or 1
                    done = True
                    break
                restarts += 1
                print(f"launch.py: replica {m['rank']} {why}; "
                      f"respawning in place (restart {restarts}/"
                      f"{args.max_restarts}); the router re-"
                      "dispatches its in-flight requests meanwhile",
                      file=sys.stderr)
                spawn("replica", m["rank"])
            if done:
                break
            if next_status is not None and now >= next_status:
                next_status = now + args.status_interval
                snaps = _collect_snapshots(
                    {k: m["hb"] for k, m in members.items()
                     if m["hb"] is not None})
                healthy = sum(1 for m in members.values()
                              if m["role"] == "replica"
                              and hb_fresh(m, now))
                print(_fleet_status(snaps, healthy, n, rate_state),
                      file=sys.stderr)
            time.sleep(0.05)
        return rc
    finally:
        # SIGTERM = drain: the router snapshots + drains the fleet,
        # replicas snapshot-then-drain their own engines
        procs = [m["proc"] for m in members.values()]
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 10
        for p in procs:
            while p.poll() is None and time.time() < deadline:
                time.sleep(0.05)
            if p.poll() is None:
                p.kill()
        if hb_dir is not None:
            print(_format_report(_collect_snapshots(
                {k: m["hb"] for k, m in members.items()
                 if m["hb"] is not None})), file=sys.stderr)


# ---------------------------------------------------------------------------
# remote data-service fleet (--data-hosts, docs/data_service.md
# "Remote ranks")
#
# One RemoteShardServer per hostfile entry ("host [shards]"), each
# serving that many decode shard streams to the training ranks over
# the framed RPC.  The launcher exports the resulting
# MXTPU_DATA_REMOTE_ADDRS to every training rank, so any
# DataServiceIter in the job homes its last shards on the fleet.
# Like a serving replica (and unlike a training rank), a data host is
# independent — its shards re-home to survivors or local workers
# while it is down — so a dead or hung server respawns *in place* on
# the SAME port (the exported addrs stay valid and the iterators'
# failover reconnects) under the --max-restarts ledger.
# ---------------------------------------------------------------------------

_LOCAL_HOSTS = ("localhost", "127.0.0.1", "::1")


class _DataFleet:
    """Spawns and supervises the --data-hosts decode servers."""

    def __init__(self, args, hosts, hb_dir):
        self.args = args
        self.hb_dir = hb_dir
        self.restarts = 0
        self.members = []
        for i, (host, slots) in enumerate(hosts):
            self.members.append({
                "idx": i, "host": host, "slots": max(slots, 1),
                # fixed port per host: the exported addr must survive
                # a respawn, and an ssh-spawned server's ephemeral
                # port-file would live on the wrong machine
                "port": args.port + 1000 + i,
                "proc": None, "hb": None, "gen": -1,
                "killed": False})

    def addrs(self):
        """The MXTPU_DATA_REMOTE_ADDRS value (one shard stream per
        slot: a host with K slots appears K times)."""
        return ",".join(f"{m['host']}:{m['port']}"
                        for m in self.members
                        for _ in range(m["slots"]))

    def _port_file(self, m):
        if self.hb_dir is None or not self._is_local(m):
            return None
        return os.path.join(self.hb_dir,
                            f"dataport-{m['idx']}-{m['gen']}")

    @staticmethod
    def _is_local(m):
        return m["host"] in _LOCAL_HOSTS

    def _spawn(self, m):
        m["gen"] += 1
        m["killed"] = False
        prog = [sys.executable, "-m",
                "incubator_mxnet_tpu.data_service.net",
                "--host", "0.0.0.0", "--port", str(m["port"]),
                "--shards", str(m["slots"]),
                "--name", f"data-{m['idx']}"]
        pf = self._port_file(m)
        if pf is not None:
            prog += ["--port-file", pf]
        extra = {}
        if self.hb_dir is not None:
            # heartbeat files need the monitor's filesystem: only
            # local-spawned servers get hung-host detection (the same
            # documented de-scope as ssh-mode training workers)
            m["hb"] = _hb_path(self.hb_dir, m["gen"],
                               f"data-{m['idx']}")
            extra["MXTPU_HEARTBEAT_FILE"] = m["hb"]
            extra["MXTPU_HEARTBEAT_INTERVAL"] = \
                str(self.args.heartbeat_interval)
            extra["MXTPU_DEBUGZ_PORTFILE"] = _dz_portfile(m["hb"])
        if self._is_local(m):
            env = dict(os.environ)
            env.update(extra)
            m["proc"] = subprocess.Popen(prog, env=env)
        else:
            m["hb"] = None      # remote file; not visible here
            if os.environ.get("PYTHONPATH"):
                extra.setdefault("PYTHONPATH",
                                 os.environ["PYTHONPATH"])
            assigns = " ".join(f"{k}={shlex.quote(v)}"
                               for k, v in sorted(extra.items()))
            prog_s = " ".join(shlex.quote(c) for c in prog)
            rc = (f"cd {shlex.quote(os.getcwd())} && "
                  f"{assigns} exec {prog_s}").replace("  ", " ")
            m["proc"] = subprocess.Popen(
                _ssh_argv(self.args, m["host"], rc))

    def spawn_all(self, wait_s=20.0):
        for m in self.members:
            self._spawn(m)
        # port-file handshake for local servers: the first epoch
        # command must not race the listener's bind (a lost race is
        # survivable — the shard fails over — but burns restart
        # budget on a healthy fleet)
        deadline = time.time() + wait_s
        for m in self.members:
            pf = self._port_file(m)
            if pf is None:
                continue
            while not os.path.exists(pf) \
                    and time.time() < deadline \
                    and m["proc"].poll() is None:
                time.sleep(0.05)
            if not os.path.exists(pf):
                print(f"launch.py: data host {m['host']} did not "
                      f"write its port file within {wait_s:.0f}s; "
                      "its shards will fail over until it comes up",
                      file=sys.stderr)

    def poll(self, now):
        """One monitor tick: kill hung servers (stale heartbeat),
        respawn dead ones in place under the shared restart ledger."""
        for m in self.members:
            p = m["proc"]
            if p is None:
                continue        # budget spent: permanently down
            if p.poll() is None:
                if self.args.heartbeat_timeout > 0 \
                        and m["hb"] is not None and not m["killed"]:
                    try:
                        age = now - os.path.getmtime(m["hb"])
                    except OSError:
                        continue     # no heartbeat yet: unmonitored
                    if age > self.args.heartbeat_timeout:
                        print(f"launch.py: data host {m['host']} "
                              f"hung (no heartbeat for {age:.0f}s > "
                              f"{self.args.heartbeat_timeout:.0f}s);"
                              " killing it", file=sys.stderr)
                        p.kill()
                        m["killed"] = True
                continue
            why = "hung (killed)" if m["killed"] \
                else f"exited with {p.poll()}"
            if self.restarts >= self.args.max_restarts:
                print(f"launch.py: data host {m['host']} {why}; "
                      f"restart budget spent ({self.restarts}/"
                      f"{self.args.max_restarts}); its shards stay "
                      "re-homed on the training ranks",
                      file=sys.stderr)
                m["proc"] = None
                continue
            self.restarts += 1
            print(f"launch.py: data host {m['host']} {why}; "
                  f"respawning on port {m['port']} (restart "
                  f"{self.restarts}/{self.args.max_restarts}); its "
                  "shards re-home until it answers",
                  file=sys.stderr)
            self._spawn(m)

    def snapshots(self):
        """host label -> telemetry snapshot (local servers only)."""
        snaps = {}
        for m in self.members:
            if m["hb"] is None:
                continue
            _, snap = _read_heartbeat(m["hb"])
            if snap is not None:
                snaps[f"data-{m['idx']}"] = snap
        return snaps

    def telemetry(self):
        """(remote img/s summed over hosts, fleet restarts, healthy
        count, total) for the status line."""
        img_s = 0.0
        for snap in self.snapshots().values():
            img_s += (snap.get("gauges") or {}).get(
                "data_service_remote_img_per_sec", 0.0) or 0.0
        healthy = sum(1 for m in self.members
                      if m["proc"] is not None
                      and m["proc"].poll() is None
                      and not m["killed"])
        return img_s, self.restarts, healthy, len(self.members)

    def report_lines(self):
        lines = []
        snaps = self.snapshots()
        for m in self.members:
            alive = m["proc"] is not None \
                and m["proc"].poll() is None
            snap = snaps.get(f"data-{m['idx']}")
            img_s = ((snap.get("gauges") or {}).get(
                "data_service_remote_img_per_sec", 0.0) or 0.0) \
                if snap else 0.0
            frames = ((snap.get("counters") or {}).get(
                "data_service_net_frames_total", 0)) if snap else 0
            lines.append(
                f"launch.py:   data host {m['host']}:{m['port']}: "
                + ("up" if alive else "down")
                + f" shards={m['slots']}"
                + (f" {img_s:.0f} img/s" if img_s else "")
                + (f" frames={frames}" if frames else ""))
        if self.restarts:
            lines.append(f"launch.py:   data-host restarts: "
                         f"{self.restarts}")
        return lines

    def stop(self):
        procs = [m["proc"] for m in self.members
                 if m["proc"] is not None]
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 10
        for p in procs:
            while p.poll() is None and time.time() < deadline:
                time.sleep(0.05)
            if p.poll() is None:
                p.kill()


def main():
    ap = argparse.ArgumentParser(
        description="Launch a distributed training job")
    ap.add_argument("-n", "--num-workers", type=int, default=None,
                    help="number of worker processes (required "
                    "except with --serve-fleet)")
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="ignored (no parameter servers; kept for "
                    "CLI parity with the reference)")
    ap.add_argument("--launcher", default="local",
                    choices=["local", "ssh", "mpi", "sge", "yarn"])
    ap.add_argument("-H", "--hostfile", default=None,
                    help="hostfile for ssh/mpi modes: 'host [slots]' "
                    "per line")
    ap.add_argument("--port", type=int, default=29500,
                    help="coordinator port on rank 0's host "
                    "(ssh/mpi modes; local mode picks a free port)")
    ap.add_argument("--ssh-cmd", default="ssh",
                    help="remote-shell command for --launcher ssh "
                    "(e.g. 'gcloud compute tpus tpu-vm ssh')")
    ap.add_argument("--env", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="extra env var to propagate to every worker "
                    "(repeatable)")
    ap.add_argument("--heartbeat-timeout", type=float,
                    default=_env_float("MXTPU_HEARTBEAT_TIMEOUT", 60.0),
                    help="local mode: kill a worker whose heartbeat "
                    "file (written by dist.init's beat thread) is "
                    "staler than this many seconds — distinguishes "
                    "hung workers from crashed ones; 0 disables")
    ap.add_argument("--heartbeat-interval", type=float,
                    default=_env_float("MXTPU_HEARTBEAT_INTERVAL", 2.0),
                    help="seconds between worker heartbeat refreshes")
    ap.add_argument("--status-interval", type=float,
                    default=_env_float("MXTPU_STATUS_INTERVAL", 30.0),
                    help="local mode: seconds between aggregated "
                    "cluster status lines built from the telemetry "
                    "snapshots riding the worker heartbeat files "
                    "(throughput, stragglers, error counters); 0 "
                    "disables; a final run report always prints on "
                    "exit when telemetry is available")
    ap.add_argument("--data-timeout", type=float, default=None,
                    help="export MXTPU_DATA_TIMEOUT to every worker: "
                    "input-pipeline queue waits past this many "
                    "seconds raise DataPipelineError (a restartable "
                    "failure) instead of hanging; unset leaves the "
                    "workers' own env/default")
    ap.add_argument("--data-workers", type=int, default=None,
                    help="export MXTPU_DATA_WORKERS to every worker: "
                    "decode worker processes each rank's "
                    "DataServiceIter spawns (the sharded "
                    "multi-process input service, "
                    "docs/data_service.md); unset leaves the "
                    "workers' own env/default")
    ap.add_argument("--data-hosts", default=None, metavar="HOSTFILE",
                    help="remote decode fleet (docs/data_service.md "
                    "\"Remote ranks\"): spawn one data_service.net "
                    "server per hostfile line ('host [shards]' — "
                    "localhost entries spawn directly, others over "
                    "--ssh-cmd) on fixed ports derived from --port, "
                    "and export MXTPU_DATA_REMOTE_ADDRS to every "
                    "training rank so their DataServiceIter homes "
                    "its last shards on the fleet.  Dead/hung "
                    "servers respawn in place on the same port "
                    "under --max-restarts while the shards fail "
                    "over; requires --launcher local or ssh")
    ap.add_argument("--nonfinite-policy", default=None,
                    choices=["off", "warn", "skip", "raise"],
                    help="export MXTPU_NONFINITE_POLICY to every "
                    "worker: arm the training-step sentinel (skip "
                    "non-finite updates, detect divergence — "
                    "docs/numeric_stability.md)")
    ap.add_argument("--max-bad-steps", type=int, default=None,
                    help="export MXTPU_MAX_BAD_STEPS to every "
                    "worker: consecutive non-finite steps before a "
                    "worker rolls back to its newest valid "
                    "checkpoint and exits with the divergence code")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="relaunch the whole job up to N times after "
                    "a worker crash or divergence (workers resume "
                    "from their last checkpoint; collective training "
                    "cannot continue around a dead rank, so restart "
                    "is whole-job, the reference's scheduler-restart "
                    "model)")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic restarts (docs/elastic.md): a "
                    "crashed/hung rank shrinks the next world to the "
                    "surviving rank set; a worker exiting with the "
                    "elastic code (14: coordinated collective abort "
                    "or a deliberate restart request) relaunches the "
                    "full target world, re-admitting replaced "
                    "workers at the checkpoint boundary the restart "
                    "resumes from.  Workers see MXTPU_ELASTIC=1 and "
                    "a fresh MXTPU_WORLD_GENERATION per world; "
                    "requires reshardable (sharded-manifest) "
                    "checkpoints to resume onto the changed world")
    ap.add_argument("--serve-fleet", type=int, default=None,
                    metavar="N",
                    help="serving fleet mode (docs/serving.md "
                    "\"Fleet\"): run the command N+1 times on this "
                    "host — one router plus N replica workers — "
                    "wired through MXTPU_FLEET_ROLE / "
                    "MXTPU_ROUTER_PORT / MXTPU_REPLICA_ADDRS / "
                    "MXTPU_REPLICA_PORT (the command switches on "
                    "the role).  A dead or hung replica respawns in "
                    "place under the --max-restarts ledger while the "
                    "router re-dispatches its in-flight requests; "
                    "the router's exit decides the job (0 stops the "
                    "replicas and succeeds)")
    ap.add_argument("--max-elastic-restarts", type=int, default=3,
                    help="elastic restarts budget (counted and "
                    "logged separately from --max-restarts, which "
                    "keeps counting crashes without --elastic and "
                    "divergence always)")
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="training command")
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")
    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if args.serve_fleet is None and args.num_workers is None:
        ap.error("-n/--num-workers is required (except with "
                 "--serve-fleet)")

    if 0 < args.heartbeat_timeout < 2 * args.heartbeat_interval:
        # a worker sleeping one interval must never look hung — the
        # monitor would SIGKILL every healthy worker and burn the
        # whole --max-restarts budget on a fine job
        ap.error(
            f"--heartbeat-timeout ({args.heartbeat_timeout:g}s) must "
            f"be at least twice --heartbeat-interval "
            f"({args.heartbeat_interval:g}s), or 0 to disable")

    hb_dir = None
    if args.launcher == "local" and args.heartbeat_timeout > 0:
        # heartbeat files only work where the monitor shares a
        # filesystem with the workers — local mode; ssh-mode hosts
        # would need a side channel (documented de-scope,
        # docs/resilience.md)
        import tempfile
        hb_dir = tempfile.mkdtemp(prefix="mxtpu_hb_")

    if args.serve_fleet is not None:
        if args.launcher != "local":
            ap.error("--serve-fleet requires --launcher local")
        if args.serve_fleet < 1:
            ap.error("--serve-fleet wants N >= 1 replicas")
        try:
            return _run_fleet(args, cmd, hb_dir)
        finally:
            if hb_dir is not None:
                shutil.rmtree(hb_dir, ignore_errors=True)

    data_fleet = None
    if args.data_hosts:
        if args.launcher not in ("local", "ssh"):
            ap.error("--data-hosts requires --launcher local or ssh")
        data_hosts = _parse_hostfile(args.data_hosts)
        data_fleet = _DataFleet(args, data_hosts, hb_dir)
        data_fleet.spawn_all()
        # every training rank sees the fleet: DataServiceIter homes
        # its LAST len(addrs) shards on these servers
        args.env.append(
            f"MXTPU_DATA_REMOTE_ADDRS={data_fleet.addrs()}")
        print(f"launch.py: data fleet: {len(data_hosts)} host(s), "
              f"{data_fleet.addrs().count(',') + 1} shard "
              f"stream(s) at {data_fleet.addrs()}", file=sys.stderr)

    if args.launcher == "local":
        def make_spawners(coord, attempt, world):
            spawners = []
            for r in range(world):
                env = dict(os.environ)
                env.update(_worker_env(args, r, coord, attempt,
                                       world))
                if hb_dir is not None:
                    hb = _hb_path(hb_dir, attempt, r)
                    env["MXTPU_HEARTBEAT_FILE"] = hb
                    env["MXTPU_HEARTBEAT_INTERVAL"] = \
                        str(args.heartbeat_interval)
                    env["MXTPU_DEBUGZ_PORTFILE"] = _dz_portfile(hb)

                def spawn(env=env):
                    return subprocess.Popen(cmd, env=env)
                spawners.append(spawn)
            return spawners

        def coord_for(attempt):
            return f"127.0.0.1:{_free_port()}"

    elif args.launcher == "ssh":
        if not args.hostfile:
            ap.error("--launcher ssh requires -H/--hostfile")
        hosts_all = _parse_hostfile(args.hostfile)
        # elastic ssh state: the live host pool shrinks when a
        # rank's host fails (its machine may be gone — re-spawning
        # on it would burn the whole elastic budget against a dead
        # box) and is restored in full on a grow restart; the rank
        # assignment AND the coordinator re-derive from the live
        # pool each attempt, so the coordinator never stays pinned
        # to a failed host
        ssh_live = {"hosts": list(hosts_all), "ranks": []}

        def coord_for(attempt):
            host = _assign_hosts(ssh_live["hosts"], 1)[0]
            return f"{host}:{args.port + attempt}"

        def make_spawners(coord, attempt, world):
            ranks = _assign_hosts(ssh_live["hosts"], world)
            ssh_live["ranks"] = ranks
            spawners = []
            for r in range(world):
                argv = _ssh_argv(
                    args, ranks[r],
                    _remote_command(args, r, coord, attempt, cmd,
                                    world))

                def spawn(argv=argv):
                    return subprocess.Popen(argv)
                spawners.append(spawn)
            return spawners

        def drop_failed_hosts(failed):
            assigned = ssh_live["ranks"]
            bad = {assigned[r] for r in failed
                   if r < len(assigned)}
            live = [(h, s) for h, s in ssh_live["hosts"]
                    if h not in bad]
            if live:
                ssh_live["hosts"] = live
                print(f"launch.py: excluding failed host(s) "
                      f"{sorted(bad)} from the next world",
                      file=sys.stderr)

        def restore_hosts():
            ssh_live["hosts"] = list(hosts_all)

    elif args.launcher == "mpi":
        mpirun = shutil.which("mpirun")
        argv = ["mpirun", "-np", str(args.num_workers)]
        # coordinator must live where mpirun places rank 0: with a
        # hostfile that is its first host (mpirun fills hosts in
        # order); otherwise single-host, this machine
        coord_host = socket.gethostname()
        if args.hostfile:
            argv += ["--hostfile", args.hostfile]
            coord_host = _parse_hostfile(args.hostfile)[0][0]
        coord = f"{coord_host}:{args.port}"
        env = _worker_env(args, -1, coord, 0)
        env.pop("MXTPU_WORKER_RANK")
        # ranks are assigned by the MPI runtime; dist.init() reads
        # OMPI_COMM_WORLD_RANK/PMIX_RANK/PMI_RANK/SLURM_PROCID when
        # this flag is set (dist._env_rank)
        env["MXTPU_RANK_FROM_MPI"] = "1"
        for k, v in sorted(env.items()):
            argv += ["-x", f"{k}={v}"]
        argv += cmd
        if mpirun is None:
            print("launch.py: mpirun not found; equivalent command:",
                  file=sys.stderr)
            print(" ".join(shlex.quote(a) for a in argv))
            return 127
        return subprocess.call(argv)

    else:   # sge / yarn: site-specific submission APIs (documented)
        coord = f"<rank0-host>:{args.port}"
        print(f"# {args.launcher} mode: submit one task per line "
              "(rank 0's host is the coordinator):")
        for r in range(args.num_workers):
            print(_remote_command(args, r, coord, 0, cmd))
        return 0

    if args.launcher == "local":
        # single host: shrink/grow only changes the world size
        def drop_failed_hosts(failed):
            pass

        def restore_hosts():
            pass

    def hb_files(attempt, world):
        if hb_dir is None:
            return None
        return {r: _hb_path(hb_dir, attempt, r)
                for r in range(world)}

    # restart ledger: crashes/divergence count against
    # --max-restarts (unchanged semantics), elastic world changes
    # against their own budget with their own log line, so an
    # operator reading the log can tell "the world resized twice"
    # from "it crashed twice" at a glance
    world = args.num_workers
    attempt = 0
    crash_restarts = 0
    elastic_restarts = 0
    try:
        while True:
            last_files = hb_files(attempt, world)
            rc, failed = _run_once(
                make_spawners(coord_for(attempt), attempt, world),
                last_files, args.heartbeat_timeout,
                args.status_interval, data_fleet=data_fleet)
            if rc == 0:
                break
            if args.elastic and rc not in (DIVERGED_EXIT, OOM_EXIT):
                if elastic_restarts >= args.max_elastic_restarts:
                    print("launch.py: elastic restart budget spent "
                          f"({elastic_restarts}/"
                          f"{args.max_elastic_restarts}); giving up",
                          file=sys.stderr)
                    break
                elastic_restarts += 1
                prev_world = world
                if rc == ELASTIC_EXIT:
                    # coordinated abort / deliberate restart request:
                    # the rank that exited 14 is healthy — relaunch
                    # the full target world, re-admitting any
                    # previously shrunk-out worker (and host) at the
                    # checkpoint boundary the resume lands on
                    world = args.num_workers
                    restore_hosts()
                    why = "grow: re-admitting replaced worker(s) " \
                        "at the checkpoint boundary" \
                        if world > prev_world else \
                        "coordinated abort: same world"
                else:
                    world = max(1, prev_world - max(1, len(failed)))
                    drop_failed_hosts(failed)
                    why = (f"shrink: rank(s) {sorted(failed)} "
                           "failed") if failed else \
                        "shrink: a rank was lost"
                print(f"launch.py: ELASTIC restart "
                      f"{elastic_restarts}/"
                      f"{args.max_elastic_restarts}: world "
                      f"{prev_world} -> {world} ({why}); workers "
                      "resume from the newest sharded checkpoint "
                      "generation, resharded onto the new world",
                      file=sys.stderr)
            else:
                if crash_restarts >= args.max_restarts:
                    break
                crash_restarts += 1
                if rc == OOM_EXIT:
                    print(f"launch.py: worker reported OUT OF "
                          f"MEMORY (exit {rc}): device HBM "
                          "exhausted past the preflight ladder and "
                          "the one-rung runtime retry; the flight-"
                          "recorder post-mortem carries the "
                          "predicted-vs-actual memory plan "
                          "(docs/memory.md).  OOM is deterministic "
                          "— restarting (attempt "
                          f"{crash_restarts}/{args.max_restarts}) "
                          "rarely helps unless batch size, model, "
                          "or MXTPU_HBM_BYTES changed",
                          file=sys.stderr)
                elif rc == DIVERGED_EXIT:
                    print(f"launch.py: worker reported DIVERGENCE "
                          f"(exit {rc}: MXTPU_MAX_BAD_STEPS "
                          "consecutive non-finite steps); params "
                          "were rolled back to the newest valid "
                          "checkpoint — restarting (attempt "
                          f"{crash_restarts}/{args.max_restarts}) "
                          "resumes from it", file=sys.stderr)
                else:
                    print("launch.py: restarting job (attempt "
                          f"{crash_restarts}/{args.max_restarts}); "
                          "workers should resume from their last "
                          "checkpoint (params + optimizer .states + "
                          "input-pipeline .data companions)",
                          file=sys.stderr)
            attempt += 1
        # final run report from the exited workers' last snapshots
        # (the heartbeat files persist until the cleanup below)
        if last_files:
            print(_format_report(_collect_snapshots(last_files)),
                  file=sys.stderr)
        if data_fleet is not None:
            for line in data_fleet.report_lines():
                print(line, file=sys.stderr)
        return rc
    finally:
        if data_fleet is not None:
            data_fleet.stop()
        if hb_dir is not None:
            shutil.rmtree(hb_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
