#!/usr/bin/env python
"""Static checks (ref role: the reference's Jenkinsfile lint stage,
which runs pylint/cpplint).  No third-party linters exist in this
image, so this is a stdlib AST linter covering the defects that
matter for this codebase: syntax errors, unused imports, wildcard
imports, duplicate function definitions in a class body, and
accidental tabs / trailing whitespace.

Exit code 0 = clean.  Usage: python ci/lint.py [paths...]
"""
import ast
import sys
from pathlib import Path

DEFAULT_PATHS = ["incubator_mxnet_tpu", "tools", "examples", "ci",
                 "chip_smoke.py", "__graft_entry__.py"]
MAX_LINE = 100

# Framework modules that write checkpoint/state files.  In these,
# a bare ``open(path, "wb")`` is forbidden: a crash mid-write leaves
# a truncated file that poisons the next resume.  All checkpoint
# bytes must flow through resilience.atomic_save/atomic_write_bytes
# (temp + fsync + rename + CRC32 sidecar).
CKPT_MODULES = (
    "incubator_mxnet_tpu/model.py",
    "incubator_mxnet_tpu/kvstore.py",
    "incubator_mxnet_tpu/callback.py",
    "incubator_mxnet_tpu/ndarray/ndarray.py",
    "incubator_mxnet_tpu/gluon/parameter.py",
    "incubator_mxnet_tpu/gluon/trainer.py",
    "incubator_mxnet_tpu/gluon/block.py",
    "incubator_mxnet_tpu/module/",
    # the sharded-manifest checkpoint writer (docs/elastic.md): a
    # torn shard or manifest must be impossible by construction
    "incubator_mxnet_tpu/parallel/checkpoint.py",
)

# Input-pipeline modules.  In these, a bare ``queue.get()`` with no
# timeout is forbidden: a producer that died or wedged leaves the
# consumer blocked forever, which on a TPU pod looks like a hung job
# the heartbeat monitor cannot distinguish from real work.  All
# prefetch-queue reads must go through io.io._bounded_get (deadline +
# dead-thread detection -> typed DataPipelineError).
DATA_QUEUE_DIRS = (
    "incubator_mxnet_tpu/io/",
    "incubator_mxnet_tpu/gluon/data/",
    # serving request queues: a wedged submitter must never hang the
    # scheduler loop
    "incubator_mxnet_tpu/serving/",
    # data-service shared-memory rings: every consumer wait must be
    # deadline-aware (ring.get) or it hangs on a SIGKILLed worker
    "incubator_mxnet_tpu/data_service/",
)

# In the data-service ring modules the blocking primitive is a
# multiprocessing semaphore, not a queue: a bare ``.acquire()`` with
# no timeout is the same eternal-block hazard as an unbounded
# ``queue.get()`` (a SIGKILLed producer never releases), so every
# acquire must pass a timeout and poll (ring.get / _acquire_free).
# Deliberate exceptions carry `# deadline-ok: <why>` on the line.
SEM_ACQUIRE_DIRS = (
    "incubator_mxnet_tpu/data_service/",
)

# Guarded training hot paths (step sentinel,
# docs/numeric_stability.md).  In these functions an *unconditional*
# host sync — .item()/.asscalar()/.asnumpy(), np.asarray on a device
# value, jax.device_get — would turn every training step into a
# device->host round trip; the sentinel's design budget is ONE scalar
# read per MXTPU_GUARD_INTERVAL steps.  The guard-interval read
# itself is annotated `# sync-ok: <why>` on its line.
HOT_SYNC_FILES = (
    "incubator_mxnet_tpu/gluon/trainer.py",
    "incubator_mxnet_tpu/optimizer.py",
    # serving hot paths: the continuous-batching loop budgets ONE
    # device->host read per iteration (the token read, annotated
    # sync-ok); anything else would serialize the decode stream
    "incubator_mxnet_tpu/serving/engine.py",
    "incubator_mxnet_tpu/serving/scheduler.py",
    # flight recorder: memory sampling rides the heartbeat and must
    # read array METADATA only — an accidental device sync here
    # would stall the hot paths every beat
    "incubator_mxnet_tpu/tracing.py",
    # introspection plane: every debugz op is zero-device-sync by
    # contract — a varz/statusz poll against a busy rank must never
    # stall the step or decode loop (docs/observability.md
    # "Introspection plane")
    "incubator_mxnet_tpu/debugz.py",
)
HOT_SYNC_FUNCS = {"step", "update", "__call__", "begin_step",
                  "guarded_step_begin", "read_window_bad",
                  "accumulate_window", "all_finite",
                  # serving scheduler loop + decode step
                  "_admit", "_admit_one", "_grow", "_decode_once",
                  "_append_token",
                  "_retire", "_preempt", "_fail", "stream", "run",
                  # serving survival layer: the reap sweep and every
                  # terminal path run inside the engine iteration,
                  # and drain/snapshot/cancel may run under SIGTERM —
                  # none may add a device->host sync
                  "_reap", "_release", "_expire", "_cancel_now",
                  "_finalize", "drain", "_latch_drain", "cancel",
                  "snapshot", "stream_request", "_stream_gen",
                  # tracing producers + memory sampling
                  "trace_event", "record", "device_memory_stats",
                  "update_memory_gauges", "_rss_bytes",
                  # debugz op handlers + dispatch + provider fan-in:
                  # the whole introspection read path is host-side
                  "_handle", "_status_payload", "_op_varz",
                  "_op_statusz", "_op_tracez", "_op_memz",
                  "_op_profilez", "_op_healthz",
                  # anomaly watchdog: fed on every train step and
                  # every emitted serving token
                  "observe", "verdicts"}
# attrs that always sync, and ones that sync only for specific roots
SYNC_ATTRS = {"item", "asscalar", "asnumpy"}
SYNC_ROOT_ATTRS = {("np", "asarray"), ("numpy", "asarray"),
                   ("jax", "device_get")}

# Serving RPC transport files (docs/serving.md "Fleet").  In these,
# an unbounded socket wait — .recv()/.accept()/.connect()/
# .create_connection() with no timeout kwarg — is forbidden: a peer
# that died mid-frame would park the reader (or the router's dispatch
# path) forever, which the fleet reads as a healthy-but-silent
# replica.  Every wait must arm the per-call deadline
# (rpc._deadline + settimeout) or pass timeout=; a deliberate
# exception carries `# deadline-ok: <why>` on the line or in the
# comment block directly above it.
SOCKET_WAIT_FILES = (
    "incubator_mxnet_tpu/rpc.py",
    "incubator_mxnet_tpu/serving/rpc.py",
    "incubator_mxnet_tpu/serving/router.py",
    "incubator_mxnet_tpu/serving/replica.py",
    # remote data-service ranks: a dead train host must never park a
    # shard server's stream thread (and vice versa)
    "incubator_mxnet_tpu/data_service/net.py",
    # introspection plane: the endpoint and its stdlib fleet client
    # both promise a hung peer can never hang the caller
    "incubator_mxnet_tpu/debugz.py",
    "tools/debugz.py",
)
SOCKET_WAIT_ATTRS = {"recv", "accept", "connect",
                     "create_connection"}

# Deadline/timeout modules (serving SLOs + the resilience layer's
# deadline machinery; docs/serving.md "SLOs, shedding, and drain").
# In these, bare ``time.time()`` is forbidden: the wall clock jumps
# under NTP slew/step and host suspend, so deadline or timeout
# arithmetic built on it can expire live requests en masse (or never
# expire anything).  All deadline math must use time.monotonic();
# a deliberate wall-clock STAMP (an absolute timestamp written for
# humans or cross-host readers, never subtracted against a deadline)
# carries `# wallclock-ok: <why>` on the line.
MONO_CLOCK_PATHS = (
    "incubator_mxnet_tpu/serving/",
    "incubator_mxnet_tpu/resilience.py",
    # the shared RPC transport and the remote data-plane ranks do
    # deadline arithmetic too (moved out of serving/, keep covered)
    "incubator_mxnet_tpu/rpc.py",
    "incubator_mxnet_tpu/data_service/net.py",
    # introspection plane: per-target deadlines everywhere
    "incubator_mxnet_tpu/debugz.py",
    "tools/debugz.py",
)

# MXTPU_-prefixed tokens that are NOT environment variables (log
# markers etc.) — exempt from the env-var documentation check.
NON_ENV_TOKENS = {"MXTPU_KILLED"}

# Instrumented hot-path modules (docs/observability.md).  In these,
# raw ``time.perf_counter()`` section timing is forbidden: wall-time
# sections must go through ``telemetry.span`` so they land in the
# registry AND the chrome-tracing timeline instead of a private
# variable nobody can see.  Lines annotated `# timing-ok: <why>` are
# exempt (telemetry.py and profiler.py — the timing backends — are
# not listed).
SPAN_TIMING_MODULES = (
    "incubator_mxnet_tpu/module/base_module.py",
    "incubator_mxnet_tpu/module/module.py",
    "incubator_mxnet_tpu/gluon/trainer.py",
    "incubator_mxnet_tpu/model.py",
    "incubator_mxnet_tpu/callback.py",
    "incubator_mxnet_tpu/monitor.py",
    "incubator_mxnet_tpu/io/io.py",
    "incubator_mxnet_tpu/gluon/data/dataloader.py",
)

# telemetry metric factories: a string literal passed to one of these
# is a metric (or span) name and must be declared in the catalog
# table of docs/observability.md — same discipline as the env-var
# registry, so `snapshot()` output is always documented.
METRIC_FACTORIES = {"counter", "gauge", "histogram", "span"}

# flight-recorder event factory: a string literal passed to
# tracing.trace_event is a trace-event name and must be declared in
# the event catalog of docs/observability.md — an operator reading a
# post-mortem dump must always find the event's meaning.
TRACE_EVENT_FACTORIES = {"trace_event"}

# The symbolic-IR graph is owned by the pass pipeline: outside
# incubator_mxnet_tpu/graph/ and /symbol/, code must treat `_Node`
# DAGs as read-only and rewrite them through the PassManager
# (docs/graph_passes.md).  Direct structural mutation — constructing
# or importing `_Node`, assigning `.op`/`.inputs`, list-mutating
# `.inputs`, or writing `.attrs[...]`/`.params[...]` — is flagged;
# a deliberate exception carries `# graph-ok: <why>` on the line.
GRAPH_MUTATION_DIRS = (
    "incubator_mxnet_tpu/graph/",
    "incubator_mxnet_tpu/symbol/",
)
GRAPH_NODE_ATTRS = {"op", "inputs"}
GRAPH_NODE_DICT_ATTRS = {"inputs", "attrs", "params"}
GRAPH_LIST_MUTATORS = {"append", "extend", "insert", "remove", "pop",
                       "clear", "reverse", "sort"}

# Typed OOM guard (docs/memory.md "Runtime OOM guard").  In the
# execution layers, a try/except that wraps a compile or
# device-execute call and catches broad ``Exception`` must route the
# caught exception through the typed guard
# (resilience.as_oom_error/is_oom/OomError): a real
# RESOURCE_EXHAUSTED swallowed or re-raised untyped here loses the
# predicted-vs-actual post-mortem AND the exit-15 contract the
# launcher keys on.  A deliberate broad handler carries
# `# oom-ok: <why>` on its except line.
OOM_GUARD_DIRS = (
    "incubator_mxnet_tpu/parallel/",
    "incubator_mxnet_tpu/module/",
    "incubator_mxnet_tpu/serving/",
)
# calls that compile for, or execute on, the device: the jit/AOT
# surface plus the conventional compiled-step fields (self._step is
# the built step function in both train-step classes; self._build
# traces + compiles it)
OOM_EXEC_ATTRS = {"jit", "compile", "lower", "device_put",
                  "block_until_ready"}
OOM_EXEC_SELF_ATTRS = {"_step", "_build"}
OOM_GUARD_NAMES = {"as_oom_error", "check_oom", "is_oom",
                   "OomError", "MemoryPlanError"}


def _is_binary_write_open(node):
    """True for ``open(..., "wb"/"wb+"/...)`` calls."""
    if not (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "open"):
        return False
    mode = None
    if len(node.args) >= 2:
        mode = node.args[1]
    for kw in node.keywords:
        if kw.arg == "mode":
            mode = kw.value
    return (isinstance(mode, ast.Constant)
            and isinstance(mode.value, str)
            and "w" in mode.value and "b" in mode.value)


def _attr_root(node):
    """Base Name id of an Attribute chain (``jax.x.y`` -> 'jax')."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _hot_sync_problems(path, tree, lines):
    """Flag unconditional host syncs inside the guarded training hot
    paths (HOT_SYNC_FILES x HOT_SYNC_FUNCS).  Lines carrying a
    ``sync-ok`` annotation — the guard-interval read — are exempt."""
    problems = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                or fn.name not in HOT_SYNC_FUNCS:
            continue
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            attr = node.func.attr
            root = _attr_root(node.func.value)
            hit = attr in SYNC_ATTRS or (root, attr) in SYNC_ROOT_ATTRS
            if not hit:
                continue
            line = lines[node.lineno - 1] \
                if node.lineno - 1 < len(lines) else ""
            if "sync-ok" in line:
                continue
            problems.append(
                f"{path}:{node.lineno}: host sync "
                f"'.{attr}()' in guarded hot path "
                f"'{fn.name}' — the step sentinel budgets one "
                "scalar device->host read per MXTPU_GUARD_INTERVAL "
                "steps; move it behind the guard-interval read or "
                "annotate the line with '# sync-ok: <why>'")
    return problems


def _graph_mutation_problems(path, tree, lines):
    """Flag direct `_Node` graph mutation outside the pass pipeline
    (GRAPH_MUTATION_DIRS).  Lines annotated `# graph-ok: <why>` are
    exempt; `self.<attr>` writes are a class's own state, not a graph
    rewrite, and are never flagged."""
    problems = []

    def _ok(node):
        line = lines[node.lineno - 1] \
            if node.lineno - 1 < len(lines) else ""
        return "graph-ok" in line

    def _rooted_self(node):
        while isinstance(node, ast.Attribute):
            node = node.value
        return isinstance(node, ast.Name) and node.id == "self"

    def _flag(node, what):
        problems.append(
            f"{path}:{node.lineno}: {what} — the symbolic graph is "
            "owned by the pass pipeline; rewrite through a "
            "PassManager pass in incubator_mxnet_tpu/graph/ "
            "(docs/graph_passes.md) or annotate the line with "
            "'# graph-ok: <why>'")

    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and node.id == "_Node" \
                and not _ok(node):
            _flag(node, "direct _Node use outside graph//symbol/")
        if isinstance(node, ast.ImportFrom) \
                and any(a.name == "_Node" for a in node.names) \
                and not _ok(node):
            _flag(node, "_Node import outside graph//symbol/")
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AugAssign):
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Attribute) \
                    and t.attr in GRAPH_NODE_ATTRS \
                    and not _rooted_self(t.value) and not _ok(t):
                _flag(t, f"assignment to graph-node .{t.attr}")
            if isinstance(t, ast.Subscript) \
                    and isinstance(t.value, ast.Attribute) \
                    and t.value.attr in GRAPH_NODE_DICT_ATTRS \
                    and not _rooted_self(t.value.value) \
                    and not _ok(t):
                _flag(t, f"item write into graph-node "
                         f".{t.value.attr}[...]")
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in GRAPH_LIST_MUTATORS | \
                {"update", "setdefault"} \
                and isinstance(node.func.value, ast.Attribute) \
                and node.func.value.attr in GRAPH_NODE_DICT_ATTRS \
                and not _rooted_self(node.func.value.value) \
                and not _ok(node):
            _flag(node, f"mutating call .{node.func.value.attr}."
                        f"{node.func.attr}(...) on a graph node")
    return problems


def _socket_wait_problems(path, tree, lines):
    """Flag unbounded socket waits in the serving RPC layer
    (SOCKET_WAIT_FILES x SOCKET_WAIT_ATTRS).  A call is bounded when
    it passes ``timeout=``; otherwise it needs a ``deadline-ok``
    annotation on its line or in the comment block directly above
    (the rpc.py pattern: ``settimeout`` armed from the per-call
    deadline right before the wait, annotation documenting it)."""
    problems = []

    def _annotated(lineno):
        if lineno - 1 < len(lines) \
                and "deadline-ok" in lines[lineno - 1]:
            return True
        i = lineno - 2
        while i >= 0 and lines[i].lstrip().startswith("#"):
            if "deadline-ok" in lines[i]:
                return True
            i -= 1
        return False

    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in SOCKET_WAIT_ATTRS):
            continue
        if any(kw.arg == "timeout" for kw in node.keywords):
            continue
        if _annotated(node.lineno):
            continue
        problems.append(
            f"{path}:{node.lineno}: unbounded socket "
            f".{node.func.attr}() in the serving RPC layer — a dead "
            "peer parks this wait forever; arm settimeout from the "
            "per-call deadline (rpc._deadline/_remaining) or pass "
            "timeout=, or annotate the line (or the comment block "
            "above it) with '# deadline-ok: <why>'")
    return problems


def _oom_guard_problems(path, tree, lines):
    """Flag broad ``except`` handlers around compile/device-execute
    calls (OOM_GUARD_DIRS) whose body never consults the typed OOM
    guard.  A handler passes when it references one of
    OOM_GUARD_NAMES (the as_oom_error routing pattern) or carries an
    ``oom-ok`` annotation on its except line."""
    problems = []

    def _is_exec_call(node):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            return False
        if node.func.attr in OOM_EXEC_ATTRS:
            return True
        return node.func.attr in OOM_EXEC_SELF_ATTRS \
            and isinstance(node.func.value, ast.Name) \
            and node.func.value.id == "self"

    def _broad(handler):
        if handler.type is None:        # bare except
            return True
        kinds = handler.type.elts \
            if isinstance(handler.type, ast.Tuple) \
            else [handler.type]
        for k in kinds:
            name = k.attr if isinstance(k, ast.Attribute) else (
                k.id if isinstance(k, ast.Name) else None)
            # XlaRuntimeError IS the RESOURCE_EXHAUSTED carrier —
            # catching it specifically still needs the typed routing
            if name in ("Exception", "BaseException",
                        "XlaRuntimeError"):
                return True
        return False

    for node in ast.walk(tree):
        if not isinstance(node, ast.Try):
            continue
        if not any(_is_exec_call(w)
                   for stmt in node.body for w in ast.walk(stmt)):
            continue
        for handler in node.handlers:
            if not _broad(handler):
                continue
            line = lines[handler.lineno - 1] \
                if handler.lineno - 1 < len(lines) else ""
            if "oom-ok" in line:
                continue
            if any((isinstance(w, ast.Name)
                    and w.id in OOM_GUARD_NAMES)
                   or (isinstance(w, ast.Attribute)
                       and w.attr in OOM_GUARD_NAMES)
                   for stmt in handler.body for w in ast.walk(stmt)):
                continue
            problems.append(
                f"{path}:{handler.lineno}: broad except around a "
                "compile/execute call without the typed OOM guard — "
                "a real RESOURCE_EXHAUSTED dies untyped here, "
                "losing the exit-15 contract and the predicted-vs-"
                "actual post-mortem; route it through "
                "resilience.as_oom_error/is_oom (docs/memory.md) or "
                "annotate the except line with '# oom-ok: <why>'")
    return problems


def _imported_names(tree):
    """name -> lineno for every import binding."""
    out = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[(a.asname or a.name).split(".")[0]] = node.lineno
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name != "*":
                    out[a.asname or a.name] = node.lineno
    return out


def _used_names(tree):
    # dotted usages (mod.attr) are covered too: the root of an
    # Attribute chain is itself a Name node in the walk
    return {node.id for node in ast.walk(tree)
            if isinstance(node, ast.Name)}


def check_file(path):
    problems = []
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: syntax error: {e.msg}"]

    is_init = path.name == "__init__.py"
    if not is_init:  # __init__ imports are re-exports by design
        imported = _imported_names(tree)
        used = _used_names(tree)
        # names quoted anywhere in the source (e.g. __all__, doc
        # references, getattr strings) count as used
        for name, lineno in sorted(imported.items()):
            if name in used or name.startswith("_sys"):
                continue
            if f'"{name}"' in src or f"'{name}'" in src:
                continue
            problems.append(
                f"{path}:{lineno}: unused import '{name}'")

    posix = path.as_posix()
    in_ckpt_module = any(
        posix.endswith(m) or (m.endswith("/") and m in posix)
        for m in CKPT_MODULES)
    in_data_queue_module = any(d in posix for d in DATA_QUEUE_DIRS)
    if any(posix.endswith(m) for m in HOT_SYNC_FILES):
        problems.extend(
            _hot_sync_problems(path, tree, src.splitlines()))
    if any(posix.endswith(m) for m in SOCKET_WAIT_FILES):
        problems.extend(
            _socket_wait_problems(path, tree, src.splitlines()))
    if any(d in posix for d in OOM_GUARD_DIRS):
        problems.extend(
            _oom_guard_problems(path, tree, src.splitlines()))
    if "incubator_mxnet_tpu" in posix and \
            not any(d in posix for d in GRAPH_MUTATION_DIRS):
        problems.extend(
            _graph_mutation_problems(path, tree, src.splitlines()))
    if any(m in posix if m.endswith("/") else posix.endswith(m)
           for m in MONO_CLOCK_PATHS):
        lines = src.splitlines()
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "time" \
                    and _attr_root(node.func.value) == "time":
                line = lines[node.lineno - 1] \
                    if node.lineno - 1 < len(lines) else ""
                if "wallclock-ok" in line:
                    continue
                problems.append(
                    f"{path}:{node.lineno}: time.time() in a "
                    "deadline/timeout module — the wall clock jumps "
                    "(NTP, suspend), so deadline arithmetic must use "
                    "time.monotonic(); a deliberate wall-clock stamp "
                    "needs '# wallclock-ok: <why>' on the line")
    if any(posix.endswith(m) for m in SPAN_TIMING_MODULES):
        lines = src.splitlines()
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "perf_counter" \
                    and _attr_root(node.func.value) == "time":
                line = lines[node.lineno - 1] \
                    if node.lineno - 1 < len(lines) else ""
                if "timing-ok" in line:
                    continue
                problems.append(
                    f"{path}:{node.lineno}: raw time.perf_counter() "
                    "in an instrumented hot-path module — time the "
                    "section with telemetry.span(...) so it lands in "
                    "the registry and the trace timeline, or "
                    "annotate the line with '# timing-ok: <why>'")

    for node in ast.walk(tree):
        if in_ckpt_module and _is_binary_write_open(node):
            problems.append(
                f"{path}:{node.lineno}: bare open(..., 'wb') in "
                "checkpoint-writing module — use resilience."
                "atomic_save/atomic_write_bytes so saves are "
                "atomic and checksummed")
        if any(d in posix for d in SEM_ACQUIRE_DIRS) \
                and isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in ("acquire", "wait"):
            # unbounded means NO finite timeout — acquire(True),
            # acquire(block=True) and wait(timeout=None) block just
            # as eternally as the zero-arg forms.  Non-blocking
            # acquire(False) is exempt.
            kws = {k.arg: k.value for k in node.keywords if k.arg}
            if node.func.attr == "acquire":
                block = kws.get("block", kws.get("blocking"))
                if block is None and node.args:
                    block = node.args[0]
                timeout = kws.get("timeout")
                if timeout is None and len(node.args) > 1:
                    timeout = node.args[1]
            else:
                block = None
                timeout = kws.get("timeout")
                if timeout is None and node.args:
                    timeout = node.args[0]
            nonblocking = isinstance(block, ast.Constant) \
                and block.value is False
            unbounded = timeout is None or (
                isinstance(timeout, ast.Constant)
                and timeout.value is None)
            line = src.splitlines()[node.lineno - 1] \
                if node.lineno - 1 < len(src.splitlines()) else ""
            if unbounded and not nonblocking \
                    and "deadline-ok" not in line:
                problems.append(
                    f"{path}:{node.lineno}: unbounded .{node.func.attr}"
                    "() in a data-service ring module — a SIGKILLed "
                    "producer never releases; pass a finite timeout "
                    "and poll (see ring.get), or annotate the line "
                    "with '# deadline-ok: <why>'")
        if in_data_queue_module and isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "get" \
                and not node.args and not node.keywords:
            # zero-arg .get() is queue-shaped (dict.get needs a key):
            # an unbounded wait that hangs the consumer forever when
            # the producer dies
            problems.append(
                f"{path}:{node.lineno}: unbounded queue .get() in "
                "input-pipeline module — use io.io._bounded_get "
                "(MXTPU_DATA_TIMEOUT deadline + dead-producer "
                "detection) or pass a timeout")
        if (not is_init and isinstance(node, ast.ImportFrom)
                and any(a.name == "*" for a in node.names)):
            # __init__.py wildcard re-exports are the namespace
            # pattern; anywhere else they hide provenance
            problems.append(
                f"{path}:{node.lineno}: wildcard import")
        if isinstance(node, ast.ClassDef):
            seen = {}
            for item in node.body:
                if isinstance(item, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    dec = [d for d in item.decorator_list]
                    # property setters legitimately reuse the name
                    if any(isinstance(d, ast.Attribute) and
                           d.attr in ("setter", "getter", "deleter")
                           for d in dec):
                        continue
                    if item.name in seen:
                        problems.append(
                            f"{path}:{item.lineno}: duplicate method "
                            f"'{item.name}' in class {node.name} "
                            f"(first at line {seen[item.name]})")
                    seen[item.name] = item.lineno

    for i, line in enumerate(src.splitlines(), 1):
        if "\t" in line:
            problems.append(f"{path}:{i}: tab character")
        if line != line.rstrip():
            problems.append(f"{path}:{i}: trailing whitespace")
        if len(line) > MAX_LINE:
            problems.append(
                f"{path}:{i}: line too long ({len(line)} > {MAX_LINE})")
    return problems


def _load_env_registry():
    """Load utils/env.py standalone (no package import — that would
    pull in jax) and return the registered flag names."""
    import importlib.util
    env_py = Path("incubator_mxnet_tpu/utils/env.py")
    if not env_py.exists():
        return None
    spec = importlib.util.spec_from_file_location("_mxtpu_env_lint",
                                                  env_py)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return set(mod.list_env())


def check_env_vars(files):
    """Every ``MXTPU_*`` env var referenced in code must be
    documented in docs/env_vars.md, and every flag read through the
    typed registry (``get_env(...)``) must be declared there
    (``register_env``) so ``mx.list_env()`` stays complete."""
    import re
    docs = Path("docs/env_vars.md")
    if not docs.exists():
        return []
    problems = []
    registry = _load_env_registry()
    token_re = re.compile(r"MXTPU_[A-Z][A-Z0-9_]*")
    # compare whole tokens, not substrings: an undocumented
    # MXTPU_DATA must not ride on documented MXTPU_DATA_TIMEOUT
    documented = set(token_re.findall(docs.read_text()))
    for path in files:
        posix = path.as_posix()
        if not (posix.startswith("incubator_mxnet_tpu")
                or posix.startswith("tools")):
            continue
        src = path.read_text()
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue        # reported by check_file
        lines = src.splitlines()
        for i, line in enumerate(lines, 1):
            for tok in token_re.findall(line):
                if tok in NON_ENV_TOKENS or tok in documented:
                    continue
                problems.append(
                    f"{path}:{i}: env var {tok} is not documented "
                    "in docs/env_vars.md")
        if registry is None:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str) \
                    and node.args[0].value.startswith("MXTPU_"):
                fn = node.func
                name = fn.id if isinstance(fn, ast.Name) else \
                    fn.attr if isinstance(fn, ast.Attribute) else ""
                if name == "get_env" \
                        and node.args[0].value not in registry:
                    problems.append(
                        f"{path}:{node.lineno}: get_env("
                        f"{node.args[0].value!r}) is not declared "
                        "via register_env in utils/env.py (list_env "
                        "would miss it)")
    # de-dup repeated hits of the same token on adjacent lines
    return sorted(set(problems))


# fault-injection entry points: a string literal passed as the
# SCOPE of resilience.inject()/fault_for() names an injectable fault
# scope, which must appear (as `scope:`) in the grammar table of
# docs/resilience.md — an operator writing an MXTPU_FAULT_SPEC must
# always find the scope's meaning and valid ops there.
FAULT_SCOPE_FACTORIES = {"inject", "fault_for"}


def check_op_cost_coverage(files):
    """Every canonical op name in the ops registry must have a cost
    entry in perf/cost_model.py — a FLOPs formula, membership in
    ZERO_COST, or a DEFAULT_COST entry with a non-empty escape
    reason (docs/observability.md "Perf observatory").  The elemwise
    cost tables are loop-generated at import time, so this check
    imports the real registry instead of walking the AST; it only
    runs when the lint set includes the op/cost sources (partial-tree
    lint runs in tests skip it)."""
    cost_py = Path("incubator_mxnet_tpu/perf/cost_model.py")
    if not cost_py.exists():
        return []
    touched = any(
        f.as_posix().startswith(("incubator_mxnet_tpu/ops/",
                                 "incubator_mxnet_tpu/perf/"))
        for f in files)
    if not touched:
        return []
    try:
        import importlib
        # lint runs as `python ci/lint.py` — the package root (cwd)
        # is not on sys.path automatically
        if str(Path.cwd()) not in sys.path:
            sys.path.insert(0, str(Path.cwd()))
        importlib.import_module("incubator_mxnet_tpu")
        reg = importlib.import_module(
            "incubator_mxnet_tpu.ops.registry")
        cm = importlib.import_module(
            "incubator_mxnet_tpu.perf.cost_model")
    except Exception as exc:
        return [f"{cost_py}: op-cost coverage check could not import "
                f"the op registry: {exc!r}"]
    canonical = {op.name for op in reg.OPS.values()}
    problems = [
        f"{cost_py}: op {name!r} has no cost entry — add a FLOPs "
        "formula or list it in ZERO_COST/DEFAULT_COST (with a "
        "reason)" for name in cm.coverage_gaps(canonical)]
    for name, reason in sorted(cm.DEFAULT_COST.items()):
        if not str(reason).strip():
            problems.append(
                f"{cost_py}: DEFAULT_COST[{name!r}] has an empty "
                "escape reason")
    stale = sorted((set(cm._FAMILY) | cm.ZERO_COST
                    | set(cm.DEFAULT_COST)) - canonical)
    problems.extend(
        f"{cost_py}: cost entry {name!r} matches no registered op "
        "(stale after a registry rename?)" for name in stale)
    return problems


def check_fault_scopes(files):
    """Every literal fault scope used in code must be documented in
    docs/resilience.md's injection grammar (ops may be dynamic —
    e.g. ``elastic:rank<N>`` — so only the scope is checked)."""
    docs = Path("docs/resilience.md")
    if not docs.exists():
        return []
    grammar = docs.read_text()
    problems = []
    for path in files:
        posix = path.as_posix()
        if "incubator_mxnet_tpu" not in posix \
                and "tools" not in posix:
            continue
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue        # reported by check_file
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            fn = node.func
            fname = fn.id if isinstance(fn, ast.Name) else \
                fn.attr if isinstance(fn, ast.Attribute) else ""
            if fname not in FAULT_SCOPE_FACTORIES:
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                continue
            scope = arg.value
            if f"`{scope}:" not in grammar:
                problems.append(
                    f"{path}:{node.lineno}: fault scope {scope!r} "
                    "is not documented in the injection grammar of "
                    "docs/resilience.md (declare it like "
                    "`" + scope + ":<op>`)")
    return sorted(set(problems))


def check_metric_catalog(files):
    """Every metric/span name created via the telemetry registry —
    a string literal passed to counter()/gauge()/histogram()/span()
    — must be declared (backtick-quoted) in the catalog table of
    docs/observability.md, mirroring the env-var lint: an operator
    reading a snapshot must always find the metric's meaning."""
    import re
    docs = Path("docs/observability.md")
    if not docs.exists():
        return []
    catalog = docs.read_text()
    name_re = re.compile(r"^[a-z][a-z0-9_]*$")
    # catalogued name tokens, for prefix-matching dynamically-built
    # names (e.g. `data_service_shard<N>_img_per_sec`)
    catalog_tokens = set(re.findall(r"`([a-zA-Z0-9_<>]+)`", catalog))

    def _dynamic_prefix(arg):
        """Leading literal text of a %-formatted or f-string metric
        name, or None when the arg is not such an expression."""
        if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Mod) \
                and isinstance(arg.left, ast.Constant) \
                and isinstance(arg.left.value, str):
            return arg.left.value.split("%")[0]
        if isinstance(arg, ast.JoinedStr) and arg.values \
                and isinstance(arg.values[0], ast.Constant) \
                and isinstance(arg.values[0].value, str):
            return arg.values[0].value
        return None

    problems = []
    for path in files:
        posix = path.as_posix()
        # substring, not prefix: unit tests feed tmp-dir copies of
        # framework files (same pattern as the hot-sync rule)
        if "incubator_mxnet_tpu" not in posix \
                and "tools" not in posix:
            continue
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue        # reported by check_file
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            fn = node.func
            fname = fn.id if isinstance(fn, ast.Name) else \
                fn.attr if isinstance(fn, ast.Attribute) else ""
            if fname in METRIC_FACTORIES | TRACE_EVENT_FACTORIES:
                # dynamically-built names (per-shard gauges): the
                # literal prefix must match a catalogued pattern
                # token, so even templated families stay documented
                prefix = _dynamic_prefix(node.args[0])
                if prefix is not None and len(prefix) >= 4 and \
                        not any(t.startswith(prefix)
                                for t in catalog_tokens):
                    problems.append(
                        f"{path}:{node.lineno}: dynamically-named "
                        f"metric/event starting {prefix!r} has no "
                        "catalogued pattern in docs/observability.md "
                        "(declare it like `" + prefix + "<N>_...`)")
                    continue
            if not (isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            name = node.args[0].value
            if fname in METRIC_FACTORIES and name_re.match(name) \
                    and f"`{name}`" not in catalog:
                problems.append(
                    f"{path}:{node.lineno}: metric/span name "
                    f"{name!r} is not declared in the catalog table "
                    "of docs/observability.md")
            if fname in TRACE_EVENT_FACTORIES \
                    and name_re.match(name) \
                    and f"`{name}`" not in catalog:
                problems.append(
                    f"{path}:{node.lineno}: trace-event name "
                    f"{name!r} is not declared in the event catalog "
                    "of docs/observability.md")
    return sorted(set(problems))


# anomaly watchdog names (docs/observability.md "Introspection
# plane"): the counter and the trace event the episode contract
# promises — both must stay catalogued
DEBUGZ_ANOMALY_METRICS = ("anomaly_detections_total",)
DEBUGZ_ANOMALY_EVENTS = ("anomaly",)


def check_debugz_catalog(files):
    """Every debugz op name — the ``OPS`` tuple in debugz.py (and
    its mirror in tools/debugz.py) — and every anomaly-watchdog
    metric/event must appear (backtick-quoted) in
    docs/observability.md: an operator querying a live process must
    always find the op's reply contract documented."""
    docs = Path("docs/observability.md")
    if not docs.exists():
        return []
    catalog = docs.read_text()
    problems = []
    saw_debugz = False
    for path in files:
        posix = path.as_posix()
        # substring match so tmp-dir test copies trigger the rule
        if "debugz" not in path.name:
            continue
        saw_debugz = True
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue        # reported by check_file
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == "OPS"
                    and isinstance(node.value, (ast.Tuple,
                                                ast.List))):
                continue
            for elt in node.value.elts:
                if not (isinstance(elt, ast.Constant)
                        and isinstance(elt.value, str)):
                    continue
                if f"`{elt.value}`" not in catalog:
                    problems.append(
                        f"{posix}:{elt.lineno}: debugz op "
                        f"{elt.value!r} is not documented in the "
                        "Introspection plane catalog of "
                        "docs/observability.md")
    if saw_debugz:
        for name in DEBUGZ_ANOMALY_METRICS:
            if f"`{name}`" not in catalog:
                problems.append(
                    f"docs/observability.md: anomaly metric "
                    f"{name!r} missing from the metric catalog")
        for name in DEBUGZ_ANOMALY_EVENTS:
            if f"`{name}`" not in catalog:
                problems.append(
                    f"docs/observability.md: anomaly event "
                    f"{name!r} missing from the event catalog")
    return sorted(set(problems))


def main(argv):
    roots = argv or DEFAULT_PATHS
    files = []
    for r in roots:
        p = Path(r)
        if p.is_dir():
            files.extend(sorted(p.rglob("*.py")))
        elif p.suffix == ".py":
            files.append(p)
    problems = []
    for f in files:
        problems.extend(check_file(f))
    problems.extend(check_env_vars(files))
    problems.extend(check_metric_catalog(files))
    problems.extend(check_debugz_catalog(files))
    problems.extend(check_fault_scopes(files))
    problems.extend(check_op_cost_coverage(files))
    for p in problems:
        print(p)
    print(f"lint: {len(files)} files, {len(problems)} problems")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
