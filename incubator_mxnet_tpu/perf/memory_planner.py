"""Analytic HBM planner: predict a train/inference step's peak live
device memory BEFORE compiling it (docs/memory.md).

The reference framework answered "will this fit?" only after the fact
(memonger's ``mirror`` attribute, or an OOM abort); XLA answers it
precisely but only *after* a full compile (``memory_analysis()``).
This module answers it analytically from the optimized Symbol graph —
the same topo walk + ``jax.eval_shape`` inference the cost model uses
(`perf/cost_model.py`) — so the preflight gate in
``ShardedTrainStep`` / ``SymbolTrainStep`` / ``Module`` can consult
capacity (`perf/device_db.py`) and walk the degrade ladder (enable
remat -> raise grad_accum -> typed ``MemoryPlanError``) before any
compile happens.

The model, per device:

- **params**: parameter + aux-state bytes (per-device slice bytes
  when the caller passes sharded sizes — ZeRO/tp aware).
- **grads**: one gradient per parameter byte; doubled under
  ``grad_accum`` > 1 (the scan carries an accumulator tree next to
  the micro-batch gradients).
- **optimizer**: the real optimizer-state tree's bytes (callers pass
  ``tree_bytes(opt_state)``; metadata only, no device reads).
- **activations**: the liveness term. Without remat every non-shape
  op output is retained for the backward (sum of those intervals);
  with remat only the recompute window's forward peak is live.
  Batch-carried, so divided by ``grad_accum`` (micro-batching) and
  ``batch_shards`` (the mesh's dp width).
- **inputs / outputs**: the batch; donation credits the output tree
  (donated params/opt alias their argument buffers).

Cross-check: ``xla_live_bytes(compiled.memory_analysis())`` composes
XLA's own buffer assignment into the same "peak live" number
(arguments + temp + non-aliased outputs); tests assert the analytic
plan lands within a stated tolerance on the bench train graphs.
"""
import numpy as np

from ..utils.env import get_env
from .device_db import hbm_capacity

__all__ = ["MemoryPlan", "PreflightResult", "plan_memory",
           "symbol_liveness", "jaxpr_liveness", "tree_bytes",
           "sharded_tree_bytes", "max_leaf_bytes", "xla_live_bytes",
           "next_divisor", "preflight"]

_CATEGORIES = ("params", "grads", "optimizer", "activations",
               "inputs", "outputs", "kv_pool")

# Fraction of elementwise-family op outputs that survive fusion as
# real buffers. XLA fuses long elementwise chains (layernorm
# arithmetic, gelu, softmax internals) into their consumers, so
# counting every written-out elementwise tensor overshoots badly on
# transformer graphs; calibrated against
# ``compiled.memory_analysis()`` on the bench train graphs.
_ELEMENTWISE_RETAIN = 0.5


def _prod(shape):
    out = 1
    for d in shape:
        out *= int(d)
    return out


def tree_bytes(tree):
    """Summed bytes of a pytree of arrays/ShapeDtypeStructs —
    metadata only (shape x itemsize), never a device read."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", ())
        dtype = getattr(leaf, "dtype", None)
        itemsize = np.dtype(dtype).itemsize if dtype is not None \
            else 4
        total += _prod(shape) * itemsize
    return float(total)


def _leaf_slice_bytes(leaf, sharding):
    """Largest per-device slice of one leaf under ``sharding``
    (falls back to the full size when bounds can't be derived)."""
    from ..parallel.sharding import shard_bounds
    shape = tuple(getattr(leaf, "shape", ()))
    itemsize = np.dtype(getattr(leaf, "dtype", "float32")).itemsize
    if sharding is None or not shape:
        return _prod(shape) * itemsize
    try:
        slice_elems = max(
            _prod([hi - lo for lo, hi in bounds])
            for bounds in shard_bounds(sharding, shape))
    except Exception:
        slice_elems = _prod(shape)
    return slice_elems * itemsize


def _iter_sharded_leaves(tree, shardings):
    import jax
    if shardings is not None and hasattr(shardings, "get") \
            and hasattr(tree, "items"):
        for name, leaf in tree.items():
            yield leaf, shardings.get(name)
        return
    for leaf in jax.tree_util.tree_leaves(tree):
        # concrete jax.Arrays / ShapeDtypeStructs carry their layout
        yield leaf, getattr(leaf, "sharding", None)


def sharded_tree_bytes(tree, shardings=None):
    """Per-device bytes of a tree: each leaf contributes its largest
    per-device slice, so ZeRO/tp sharding shrinks the plan exactly
    like it shrinks the chip.  Pass a name -> NamedSharding dict for
    a dict tree, or nothing to read each leaf's own ``.sharding``
    (concrete arrays, e.g. an optimizer-state pytree)."""
    return float(sum(_leaf_slice_bytes(leaf, sh)
                     for leaf, sh in _iter_sharded_leaves(
                         tree, shardings)))


def max_leaf_bytes(tree, shardings=None):
    """Largest single per-device leaf slice in a tree — the planner's
    "working gradient" bound under donation."""
    return float(max(
        (_leaf_slice_bytes(leaf, sh)
         for leaf, sh in _iter_sharded_leaves(tree, shardings)),
        default=0.0))


# ------------------------------------------------------------- liveness
def symbol_liveness(symbol, shapes, dtypes=None, input_names=None):
    """Tensor-interval liveness over a Symbol graph.

    Walks the graph in the cost model's topo order, inferring every
    tensor's shape/dtype with ``jax.eval_shape``, and returns the raw
    byte terms the planner composes:

    - ``params_bytes`` / ``inputs_bytes``: variable tensors split by
      ``input_names`` (aux states count as params),
    - ``retained_bytes``: outputs of non-shape ops — the set the
      backward pass keeps live when remat is off (elementwise-family
      outputs count at ``_ELEMENTWISE_RETAIN`` since XLA fuses most
      of those chains away),
    - ``forward_peak_bytes``: max over topo positions of the summed
      bytes of live intermediates (producer -> last consumer) — the
      recompute window remat pays instead,
    - ``outputs_bytes``: the head tensors.
    """
    import jax

    from ..symbol.symbol import _topo
    from .cost_model import ZERO_COST, _FAMILY

    shapes = dict(shapes or {})
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    known = {k: v for k, v in shapes.items()
             if k in set(arg_names) | set(aux_names)}
    arg_shapes, _, aux_shapes = symbol.infer_shape_partial(**known)
    for nm, s in list(zip(arg_names, arg_shapes)) \
            + list(zip(aux_names, aux_shapes)):
        if s is not None and nm not in shapes:
            shapes[nm] = tuple(s)
    if input_names is None:
        # default: the variables the caller gave shapes for are the
        # data inputs; everything recovered by inference is a param
        input_names = set(known) - set(aux_names)
    input_names = set(input_names)

    order = _topo(symbol._heads)
    pos = {id(n): i for i, n in enumerate(order)}
    avals = {}              # (id(node), idx) -> (shape, np.dtype)
    t_bytes = {}            # intermediate tensors: key -> bytes
    t_prod = {}             # key -> producer position
    last_use = {}           # key -> last consumer position
    retained = 0.0
    params_bytes = inputs_bytes = max_param = 0.0

    for node in order:
        if node.is_variable:
            if node.name not in shapes:
                continue
            dt = np.dtype((dtypes or {}).get(
                node.name, node.attrs.get("__dtype__", "float32")))
            shape = tuple(shapes[node.name])
            avals[(id(node), 0)] = (shape, dt)
            nbytes = _prod(shape) * dt.itemsize
            if node.name in input_names:
                inputs_bytes += nbytes
            else:
                params_bytes += nbytes
                max_param = max(max_param, nbytes)
            continue
        in_keys = [(id(n), i) for n, i in node.inputs]
        if any(k not in avals for k in in_keys):
            raise ValueError(
                f"memory_planner: unknown input shape at op "
                f"'{node.op.name}' (node '{node.name}') — pass "
                "shapes for all data variables")
        for k in in_keys:
            if k in t_bytes:
                last_use[k] = max(last_use.get(k, 0), pos[id(node)])
        structs = [jax.ShapeDtypeStruct(*avals[k]) for k in in_keys]
        params = dict(node.params)
        if node.op.needs_mode:
            params["_training"] = False
        if node.op.needs_rng:
            params["_rng"] = jax.ShapeDtypeStruct(
                (2,), np.dtype("uint32"))
        out = jax.eval_shape(
            lambda *xs, _p=params, _f=node.op.fn: _f(*xs, **_p),
            *structs)
        outs = out if isinstance(out, (tuple, list)) else [out]
        shape_only = node.op.name in ZERO_COST
        fused = _FAMILY.get(node.op.name) == "elementwise"
        for i, o in enumerate(outs):
            key = (id(node), i)
            shape, dt = tuple(o.shape), np.dtype(o.dtype)
            avals[key] = (shape, dt)
            nbytes = _prod(shape) * dt.itemsize
            t_bytes[key] = nbytes
            t_prod[key] = pos[id(node)]
            last_use[key] = pos[id(node)]
            if not shape_only:
                retained += nbytes * (_ELEMENTWISE_RETAIN if fused
                                      else 1.0)

    outputs_bytes = 0.0
    end = len(order)
    for node, idx in symbol._heads:
        key = (id(node), idx)
        if key in avals:
            shape, dt = avals[key]
            outputs_bytes += _prod(shape) * dt.itemsize
        if key in t_bytes:
            last_use[key] = end

    # sweep: +bytes at producer, -bytes after last use
    deltas = {}
    for key, b in t_bytes.items():
        deltas[t_prod[key]] = deltas.get(t_prod[key], 0.0) + b
        release = last_use[key] + 1
        deltas[release] = deltas.get(release, 0.0) - b
    live = peak = 0.0
    for p in sorted(deltas):
        live += deltas[p]
        peak = max(peak, live)

    return {"params_bytes": params_bytes,
            "inputs_bytes": inputs_bytes,
            "outputs_bytes": outputs_bytes,
            "retained_bytes": retained,
            "forward_peak_bytes": peak,
            "max_param_bytes": max_param,
            "n_nodes": len(order)}


# primitives whose outputs are real fusion-root buffers; everything
# else is treated as a fusable elementwise chain (same discount the
# Symbol-graph walk applies per op family)
_HEAVY_PRIMS = frozenset((
    "dot_general", "conv_general_dilated", "gather", "scatter",
    "scatter-add", "dynamic_slice", "dynamic_update_slice", "sort",
    "top_k"))


def jaxpr_liveness(fn, *example_args):
    """Interval liveness over ``jax.make_jaxpr(fn)`` — the
    PureBlock-path analog of :func:`symbol_liveness` for steps that
    have no Symbol graph (``ShardedTrainStep``).  Trace-time only
    (abstract shapes, nothing executes); call/scan/remat sub-jaxprs
    are walked inline and their body counted once (a scan's carry is
    the caller's accumulator term, not this one).  Returns the same
    liveness dict, with ``params_bytes``/``max_param_bytes`` left 0 —
    the caller supplies those from its real (sharded) value trees.
    """
    import jax

    closed = jax.make_jaxpr(fn)(*example_args)
    eqn_seq = []        # (eqn, counts_toward_retained)

    def flatten(jaxpr):
        for eqn in jaxpr.eqns:
            subs = []
            for v in eqn.params.values():
                vs = v if isinstance(v, (tuple, list)) else (v,)
                subs += [s for s in vs if hasattr(s, "jaxpr")]
            if "branches" in eqn.params:
                # one branch of a cond runs, and its branches share
                # their buffers (rtc.pallas_call's compiled/interpreted
                # pair has the same outputs): count one, not the sum
                subs = subs[:1]
            for s in subs:
                flatten(s.jaxpr)
            # a call eqn's outputs alias its sub-jaxpr's outputs:
            # track them for intervals, don't re-count the bytes
            eqn_seq.append((eqn, not subs))

    flatten(closed.jaxpr)
    retained = 0.0
    t_bytes, t_prod, last_use = {}, {}, {}
    for pos, (eqn, counts) in enumerate(eqn_seq):
        for v in eqn.invars:
            if hasattr(v, "val"):   # Literal: no interval to track
                continue
            if v in t_prod:
                last_use[v] = pos
        w = 1.0 if eqn.primitive.name in _HEAVY_PRIMS \
            else _ELEMENTWISE_RETAIN
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", None)
            if shape is None:
                continue
            # .itemsize, not np.dtype(): a PRNG key's dtype is jax's own
            nb = _prod(shape) * aval.dtype.itemsize
            t_bytes[v] = nb
            t_prod[v] = pos
            last_use[v] = pos
            if counts:
                retained += nb * w

    inputs_bytes = 0.0
    for v in closed.jaxpr.invars:
        aval = getattr(v, "aval", None)
        if hasattr(aval, "shape"):
            inputs_bytes += _prod(aval.shape) \
                * aval.dtype.itemsize
    outputs_bytes = 0.0
    end = len(eqn_seq)
    for v in closed.jaxpr.outvars:
        aval = getattr(v, "aval", None)
        if hasattr(aval, "shape"):
            outputs_bytes += _prod(aval.shape) \
                * aval.dtype.itemsize
        if v in t_bytes:
            last_use[v] = end

    deltas = {}
    for v, b in t_bytes.items():
        deltas[t_prod[v]] = deltas.get(t_prod[v], 0.0) + b
        release = last_use[v] + 1
        deltas[release] = deltas.get(release, 0.0) - b
    live = peak = 0.0
    for p in sorted(deltas):
        live += deltas[p]
        peak = max(peak, live)

    return {"params_bytes": 0.0,
            "inputs_bytes": inputs_bytes,
            "outputs_bytes": outputs_bytes,
            "retained_bytes": retained,
            "forward_peak_bytes": min(peak, retained),
            "max_param_bytes": 0.0,
            "n_nodes": len(eqn_seq)}


# ----------------------------------------------------------------- plan
class MemoryPlan:
    """One step's predicted peak live HBM, per device, by category."""

    __slots__ = _CATEGORIES + ("meta",)

    def __init__(self, params=0.0, grads=0.0, optimizer=0.0,
                 activations=0.0, inputs=0.0, outputs=0.0,
                 kv_pool=0.0, meta=None):
        self.params = float(params)
        self.grads = float(grads)
        self.optimizer = float(optimizer)
        self.activations = float(activations)
        self.inputs = float(inputs)
        self.outputs = float(outputs)
        self.kv_pool = float(kv_pool)
        self.meta = dict(meta or {})

    def total(self):
        return (self.params + self.grads + self.optimizer
                + self.activations + self.inputs + self.outputs
                + self.kv_pool)

    def headroom(self, device=None, margin=None):
        """Bytes to spare against the device's usable capacity
        (negative = predicted overflow)."""
        from .device_db import headroom as _headroom
        return _headroom(self.total(), device, margin)

    def as_dict(self):
        d = {c: getattr(self, c) for c in _CATEGORIES}
        d["total"] = self.total()
        d.update(self.meta)
        return d

    def describe(self):
        parts = [f"{c}={getattr(self, c) / (1 << 20):.1f}MB"
                 for c in _CATEGORIES if getattr(self, c) > 0]
        extras = [f"{k}={v}" for k, v in sorted(self.meta.items())]
        return (f"total={self.total() / (1 << 20):.1f}MB ("
                + " ".join(parts + extras) + ")")

    def __repr__(self):
        return f"MemoryPlan({self.describe()})"


def plan_memory(symbol=None, shapes=None, *, train=True, dtypes=None,
                input_names=None, liveness=None, params_bytes=None,
                max_param_bytes=None, optimizer_bytes=0.0,
                grad_accum=1, remat=False, donate=True,
                batch_shards=1, meta=None):
    """Compose a :class:`MemoryPlan` for one compiled step.

    Either pass a Symbol + shapes (the liveness pass runs here) or a
    precomputed ``liveness`` dict (:func:`symbol_liveness` output —
    lets the degrade ladder re-plan rungs without re-walking the
    graph). ``params_bytes`` overrides the graph's replicated
    parameter sizes with the caller's per-device sharded sizes;
    ``batch_shards`` is the mesh's data-parallel width (activations
    and inputs are batch-carried, so they shrink by it).

    The gradient term follows XLA's buffer assignment under
    donation: each parameter's update fuses right after its gradient
    completes, so gradient buffers overlap the donated masters and
    only the *working* gradient (largest leaf) is live at once.
    Without donation the full gradient tree materializes; under
    ``grad_accum`` > 1 a full accumulator tree (the scan carry)
    persists next to the working gradient either way.
    """
    live = liveness if liveness is not None else symbol_liveness(
        symbol, shapes, dtypes=dtypes, input_names=input_names)
    accum = max(1, int(grad_accum))
    shards = max(1, int(batch_shards))

    params = float(params_bytes if params_bytes is not None
                   else live["params_bytes"])
    max_param = float(max_param_bytes if max_param_bytes is not None
                      else live.get("max_param_bytes", 0.0))
    if not train:
        grads = 0.0
    elif accum > 1:
        grads = params + max_param
    elif donate:
        grads = max_param
    else:
        grads = params
    if train:
        base = live["forward_peak_bytes"] if remat \
            else live["retained_bytes"]
        # remat can never plan WORSE than no-remat
        base = min(base, live["retained_bytes"])
    else:
        base = live["forward_peak_bytes"]
    activations = base / accum / shards
    inputs = live["inputs_bytes"] / shards
    if train:
        # donated params/opt alias their argument buffers; without
        # donation the updated trees materialize next to the old ones
        outputs = 0.0 if donate else params + float(optimizer_bytes)
    else:
        outputs = live["outputs_bytes"] / shards
    info = {"train": bool(train), "remat": bool(remat),
            "grad_accum": accum, "batch_shards": shards,
            "n_nodes": live.get("n_nodes", 0)}
    info.update(meta or {})
    return MemoryPlan(params, grads, float(optimizer_bytes),
                      activations, inputs, outputs, meta=info)


def xla_live_bytes(mem_stats):
    """Compose a compiled executable's ``memory_analysis()`` into the
    same "peak live bytes" quantity the planner predicts: arguments +
    temporaries + non-aliased outputs. None when the backend reports
    nothing."""
    if mem_stats is None:
        return None
    try:
        arg = float(mem_stats.argument_size_in_bytes)
        out = float(mem_stats.output_size_in_bytes)
        alias = float(mem_stats.alias_size_in_bytes)
        temp = float(mem_stats.temp_size_in_bytes)
    except AttributeError:
        return None
    return arg + temp + max(0.0, out - alias)


# --------------------------------------------------------------- ladder
class PreflightResult:
    """Outcome of one preflight gate: the accepted plan plus the
    remat/grad_accum the ladder settled on and the rungs it took."""

    __slots__ = ("plan", "remat", "grad_accum", "rungs")

    def __init__(self, plan, remat, grad_accum, rungs):
        self.plan = plan
        self.remat = remat
        self.grad_accum = grad_accum
        self.rungs = list(rungs)


def next_divisor(n, current):
    """Smallest divisor of ``n`` strictly greater than ``current``
    (the ladder's next grad_accum candidate), or None."""
    n, current = int(n), int(current)
    if n <= 0:
        return None
    for d in range(current + 1, n + 1):
        if n % d == 0:
            return d
    return None


def preflight(make_plan, *, site, device=None, can_remat=False,
              batch_size=0, policy=None, remat=False, grad_accum=1,
              max_rungs=8):
    """Run the preflight HBM gate for one about-to-compile step.

    ``make_plan(remat, grad_accum)`` returns the MemoryPlan for that
    configuration. Under ``MXTPU_MEM_POLICY=degrade`` a predicted
    overflow walks the ladder deterministically: enable remat (if
    ``can_remat``), then raise grad_accum to the next divisor of
    ``batch_size``, re-planning after each rung; a ladder that runs
    dry raises ``MemoryPlanError`` carrying the full per-category
    plan. ``warn`` logs the overflow and compiles anyway; ``off``
    skips planning entirely (returns None). Each rung taken emits a
    ``mem_degrade`` flight-recorder event and bumps
    ``memory_plan_degrades_total``.

    Runs at bind/preflight time only — never on the step path — so it
    adds zero hot-path host syncs.
    """
    import logging

    if policy is None:
        policy = str(get_env("MXTPU_MEM_POLICY")).lower()
    if policy == "off":
        return None
    from .. import telemetry, tracing

    log = logging.getLogger("mxtpu.memory")
    plan = make_plan(remat, grad_accum)
    rungs = []
    capacity = hbm_capacity(device)
    while plan.headroom(device) < 0:
        if policy != "degrade":
            log.warning(
                "memory plan overflow at %s (policy=warn): %s vs "
                "capacity %.1fMB — compiling anyway", site,
                plan.describe(), capacity / (1 << 20))
            break
        if can_remat and not remat:
            remat, rung = True, "remat"
        else:
            nxt = next_divisor(batch_size, grad_accum) \
                if batch_size else None
            if nxt is None or len(rungs) >= max_rungs:
                _publish_plan(plan)
                from ..resilience import MemoryPlanError
                raise MemoryPlanError(site, plan, rungs,
                                      capacity=capacity)
            grad_accum, rung = nxt, f"grad_accum={nxt}"
        rungs.append(rung)
        telemetry.counter("memory_plan_degrades_total").inc()
        tracing.trace_event(
            "mem_degrade", site=site, rung=rung,
            predicted_bytes=plan.total(), capacity_bytes=capacity)
        log.warning(
            "memory plan overflow at %s: %s vs capacity %.1fMB — "
            "degrade ladder rung '%s'%s", site, plan.describe(),
            capacity / (1 << 20), rung,
            " (numerics change: smaller micro-batches)"
            if rung.startswith("grad_accum") else
            " (numerics unchanged; more compute)")
        plan = make_plan(remat, grad_accum)
    _publish_plan(plan)
    return PreflightResult(plan, remat, grad_accum, rungs)


def _publish_plan(plan):
    """Record the accepted (or last attempted) plan: the peak gauge
    plus the tracing-side holder the heartbeat's
    ``memory_plan_delta_bytes`` gauge measures drift against."""
    from .. import telemetry, tracing
    telemetry.gauge("memory_plan_peak_bytes").set(plan.total())
    tracing.set_memory_plan(plan.total())
