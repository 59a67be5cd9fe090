"""Multi-process bootstrap and cross-process collectives.

Role analog of the reference's ps-lite rendezvous + dist kvstore
transport (ref: tools/launch.py:64-83 spawning workers/servers with
DMLC_* env vars; src/kvstore/kvstore_dist.h:49 push/pull to servers).

TPU-native design: there are no parameter servers — processes join a
single JAX distributed runtime (`jax.distributed.initialize`, the
coordinator replacing the ps-lite scheduler) and gradient exchange is
a collective over all processes' devices (gloo on CPU hosts, ICI/DCN
on TPU pods).  The launcher (tools/launch.py here) sets the env
contract:

    MXTPU_NUM_WORKERS   number of worker processes
    MXTPU_WORKER_RANK   this process's rank
    MXTPU_COORD_ADDR    host:port of rank 0 (the coordinator)

`init()` is idempotent and a no-op for single-process runs, so the
same training script works launched directly or under the launcher —
the reference's `kv.num_workers`-driven behavior carries over.
"""
import os

__all__ = ["init", "is_initialized", "shutdown", "rank",
           "num_workers", "world_generation", "elastic_probe",
           "allreduce_sum", "allreduce_max", "broadcast", "barrier"]

_initialized = False


def env_num_workers():
    return int(os.environ.get("MXTPU_NUM_WORKERS", "1"))


def is_initialized():
    return _initialized


def _env_rank():
    """Worker rank from the launch environment.

    MXTPU_WORKER_RANK is the native contract (tools/launch.py local/
    ssh modes).  Under `--launcher mpi` the launcher cannot know ranks
    ahead of time — mpirun assigns them — so it sets
    MXTPU_RANK_FROM_MPI=1 and the rank comes from the MPI runtime's
    own env (OpenMPI/PMIx/MPICH/Slurm variants), the same contract
    the reference's tracker relies on for its mpi mode."""
    if os.environ.get("MXTPU_RANK_FROM_MPI") == "1":
        for var in ("OMPI_COMM_WORLD_RANK", "PMIX_RANK", "PMI_RANK",
                    "SLURM_PROCID"):
            if var in os.environ:
                return int(os.environ[var])
        raise RuntimeError(
            "MXTPU_RANK_FROM_MPI=1 but no MPI rank variable found "
            "(OMPI_COMM_WORLD_RANK/PMIX_RANK/PMI_RANK/SLURM_PROCID) "
            "— was this process actually started by mpirun?")
    return int(os.environ.get("MXTPU_WORKER_RANK", "0"))


def init(coordinator_address=None, num_workers_=None, rank_=None):
    """Join the distributed runtime (idempotent).

    Arguments default to the launcher's env contract; returns the
    process rank.  Single-process (no env, no args) is a no-op.

    The coordinator join is retried with exponential backoff
    (resilience.RetryPolicy env knobs): rank 0 may still be binding
    its port when late-spawned workers first connect, and transient
    DNS/socket errors are routine during elastic restarts.  The
    launcher-provided heartbeat (MXTPU_HEARTBEAT_FILE) starts here so
    the monitor can tell this process is alive even while it blocks
    in a collective.
    """
    global _initialized
    from . import resilience, telemetry
    resilience.start_heartbeat()
    # per-worker telemetry: snapshots ride the heartbeat file for the
    # launcher's aggregation; the JSONL emitter additionally starts
    # here when MXTPU_TELEMETRY_FILE is set (docs/observability.md)
    telemetry.maybe_start_emitter()
    # launcher-spawned workers report divergence with a distinct exit
    # code so launch.py's restart loop can tell it from a crash
    resilience.install_diverged_exithook()
    # live introspection endpoint (debugz): up before the jax join so
    # a rank wedged *in* the join can still answer varz/healthz
    from . import debugz
    debugz.maybe_start("train")
    import jax
    if _initialized:
        return jax.process_index()
    n = num_workers_ if num_workers_ is not None else env_num_workers()
    if n <= 1:
        return 0
    r = rank_ if rank_ is not None else _env_rank()
    coord = coordinator_address or os.environ.get("MXTPU_COORD_ADDR")
    if coord is None:
        raise RuntimeError(
            "MXTPU_NUM_WORKERS>1 but no MXTPU_COORD_ADDR; launch "
            "through tools/launch.py or pass coordinator_address")

    # retry only connection-shaped failures (coordinator still
    # binding, transient DNS/socket errors); a permanent
    # misconfiguration — bad num_processes, malformed address —
    # should fail on the first attempt, not after the full backoff
    def reset_failed_join():
        """jax sets global_state.client/.service *before* connect(),
        so a failed join leaves them populated and the next
        initialize raises 'should only be called once' — masking the
        real transient error and making the retry a no-op.
        :func:`shutdown` owns the one copy of that private-state
        teardown (it also serves elastic re-init); _initialized is
        already False here, so the reset is a pure state clear."""
        shutdown()

    def join():
        resilience.inject("dist", "init")
        try:
            resilience.call_transient_mapped(
                jax.distributed.initialize, coordinator_address=coord,
                num_processes=n, process_id=r,
                markers=resilience.JOIN_TRANSIENT_MARKERS)
        except resilience.ResilienceError:
            reset_failed_join()
            raise

    resilience.retry_call(
        join, op_name=f"dist.init(rank={r}, coord={coord})",
        retry_on=(resilience.TransientError,))
    _initialized = True
    _note_world(r, n)
    return r


def _note_world(r, n):
    """Attribute this boot's world in telemetry/tracing: under the
    launcher's elastic mode every (re)launch carries a monotonically
    increasing MXTPU_WORLD_GENERATION, so metrics and flight-recorder
    events can be pinned to the world they came from — an elastic
    restart is observable, not inferred from log archaeology."""
    from . import telemetry, tracing
    from .utils.env import get_env
    gen = get_env("MXTPU_WORLD_GENERATION")
    if gen <= 0:
        return
    telemetry.gauge("elastic_world_generation").set(gen)
    if gen > 1:
        # generation 1 is the first launch; anything later is an
        # elastic restart this worker is participating in
        telemetry.counter("elastic_restarts_total").inc()
        tracing.trace_event("elastic_world_resize", generation=gen,
                            world=n, rank=r,
                            elastic=bool(get_env("MXTPU_ELASTIC")))


def shutdown():
    """Leave the distributed runtime so a *different* world can
    re-init in this process (coordinated elastic recovery: after a
    CollectiveAbortedError the broken world's runtime state must be
    torn down before the new world's coordinator join).  Safe to call
    when never initialized; after it, :func:`init` works again with
    fresh env/arguments."""
    global _initialized
    # jax.distributed.shutdown() stops at the first part whose own
    # shutdown raises (a broken world's client does) and leaves it
    # set, and initialize() then refuses; no public call clears it
    from jax._src.distributed import global_state
    try:
        global_state.shutdown()
    except Exception:
        pass
    global_state.client = None
    global_state.service = None
    global_state.preemption_sync_manager = None
    _initialized = False


def world_generation():
    """The launcher-exported world generation (0 when this process
    is not launcher-managed)."""
    from .utils.env import get_env
    return get_env("MXTPU_WORLD_GENERATION")


def elastic_probe():
    """Per-step elastic fault hook: scope ``elastic``, op
    ``rank<N>`` — ``elastic:rank1:3:kill`` hard-kills rank 1 on its
    3rd step, the deterministic stand-in for an OOM-killed / lost
    worker (docs/elastic.md).  Free when no fault spec is set (one
    env read, no rank lookup)."""
    from . import resilience
    if not resilience.faults_active():
        return
    import jax
    r = jax.process_index() if _initialized else \
        int(os.environ.get("MXTPU_WORKER_RANK", "0"))
    resilience.inject("elastic", "rank%d" % r)


def rank():
    import jax
    return jax.process_index()


def num_workers():
    import jax
    return jax.process_count()


def _guarded(op, tag, body):
    """Run a collective body under the resilience contract.

    The fault-injection probe (``collective:<op>``) runs *inside* the
    deadline-wrapped callable, so an injected ``hang`` is cut short by
    MXTPU_COLLECTIVE_TIMEOUT exactly like a real wedged peer, and an
    injected ``error`` surfaces as TransientError for the kvstore
    retry layer.  Fast path: no faults declared and either the
    deadline is disabled or this is a single-process run — call
    straight through with zero thread overhead."""
    import jax
    from . import resilience

    multi = jax.process_count() > 1

    def entered_body():
        """The native collective.  On a multi-rank job an in-op
        transport error is *fatal*, not transient: peers may already
        have completed the op, and a rank-local retry would enter a
        fresh collective that pairs with the peers' next one —
        shape-mismatch crash at best, silently mixed reductions at
        worst.  Recovery for a broken in-flight collective belongs
        to the launcher's restart loop, never to an in-place
        retry."""
        if not multi:
            return body()
        try:
            return body()
        except resilience.ResilienceError:
            raise
        except (RuntimeError, OSError, ConnectionError) as exc:
            from . import telemetry
            telemetry.counter("collective_aborts_total").inc()
            raise resilience.CollectiveAbortedError(
                f"collective {op} (tag={tag} "
                f"rank={jax.process_index()}) failed in-op: {exc}; "
                "not retried — peers may have completed it, and "
                "re-entering would desynchronize the ranks (see "
                "docs/resilience.md)") from exc

    def checked():
        resilience.inject("collective", op)
        return entered_body()

    timeout = resilience.collective_timeout()
    if not resilience.faults_active() and (timeout <= 0 or not multi):
        return entered_body()
    try:
        return resilience.deadline_call(
            checked, timeout, op_name=f"collective {op}",
            detail=f"tag={tag} rank={jax.process_index()} "
                   f"num_workers={jax.process_count()}")
    except resilience.DeadlineExceededError as exc:
        # tag the expiry as collective-shaped: THIS rank is healthy,
        # a peer is dead or wedged — only these deadline errors may
        # take the elastic exit (14); a local deadline (disk, queue)
        # means this rank itself is sick and must look like a crash
        # so the elastic policy shrinks it out (docs/elastic.md)
        exc.collective = True
        raise


def allreduce_sum(value):
    """Sum ``value`` (array or pytree) across all processes.

    Results are re-wrapped as jax Arrays (multihost_utils fetches to
    host numpy; callers store these into NDArray._data, whose
    contract is a device array).  Runs under the
    MXTPU_COLLECTIVE_TIMEOUT deadline (see _guarded)."""
    import jax
    import jax.numpy as jnp

    def body():
        if jax.process_count() == 1:
            return value
        from jax.experimental import multihost_utils

        def red(v):
            gathered = multihost_utils.process_allgather(v)
            return jnp.asarray(gathered.sum(axis=0))
        return jax.tree_util.tree_map(red, value)
    return _guarded("allreduce", "-", body)


def allreduce_max(value):
    """Elementwise maximum of ``value`` across all processes.

    The step sentinel's rank-consistency primitive: every rank
    contributes its local bad-step window count and every rank
    receives the same global verdict, so skip decisions can never
    diverge across replicas (a rank-local skip desynchronizes
    optimizer state — the same discipline as CollectiveAbortedError
    for half-completed collectives).  Max — not sum — because the
    fused/mesh paths compute a *replicated* flag: every rank
    observes the same bad step, and summing would multiply one
    dropped update by the world size."""
    import jax
    import jax.numpy as jnp

    def body():
        if jax.process_count() == 1:
            return value
        from jax.experimental import multihost_utils
        gathered = multihost_utils.process_allgather(
            jnp.asarray(value))
        return jnp.asarray(gathered.max(axis=0))
    return _guarded("allreduce", "max", body)


def broadcast(value, root=0):
    """Every process receives ``root``'s value (array or pytree)."""
    import jax
    import jax.numpy as jnp

    def body():
        if jax.process_count() == 1:
            return value
        from jax.experimental import multihost_utils
        out = multihost_utils.broadcast_one_to_all(
            value, is_source=jax.process_index() == root)
        return jax.tree_util.tree_map(jnp.asarray, out)
    return _guarded("broadcast", f"root={root}", body)


def barrier(tag="mxtpu_barrier"):
    import jax

    def body():
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(tag)
    _guarded("barrier", tag, body)
