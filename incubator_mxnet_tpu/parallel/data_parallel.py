"""Sharded compiled training step: the kvstore='tpu' execution path.

Replaces the reference's data-parallel machinery — batch slicing in
DataParallelExecutorGroup (ref: python/mxnet/module/executor_group.py:99)
plus gradient reduction through KVStore Comm trees / ps-lite push-pull
(ref: src/kvstore/comm.h:91,471; src/kvstore/kvstore_dist.h) — with a
single pjit-compiled step over a named mesh:

- the global batch is laid out sharded over the 'dp' (and optionally
  'sp') mesh axes; parameters are laid out per ShardingRules (
  replicated for pure DP, 'tp'-sharded for tensor parallelism);
- `jax.grad` of the mean loss over the global batch makes XLA emit
  the gradient all-reduce (psum over 'dp') on ICI automatically — this
  *is* the kvstore push/pull, fused into the step;
- the functional optimizer update runs where the parameters live
  (the analog of update_on_kvstore, ref:
  src/kvstore/kvstore_dist_server.h ApplyUpdates:176).

The sync-point discipline matches the reference: the step is async
(dispatch returns immediately); reading the loss (`float(...)`) is the
WaitForVar analog.
"""
import logging

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import telemetry
from .functional import PureBlock, functionalize
from .mesh import (current_mesh, make_mesh, shard_batch,
                   use_mesh)
from . import optim as foptim
from .sharding import ShardingRules

__all__ = ["ShardedTrainStep"]


def _default_loss(outputs, labels):
    """Softmax cross-entropy on logits (config-1/2 default)."""
    logits = outputs[0].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1],
                            dtype=logits.dtype)
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


def _cast_floats(tree, dtype):
    """Cast float leaves of a pytree to ``dtype`` (ints untouched)."""
    def cast(v):
        if jnp.issubdtype(v.dtype, jnp.floating):
            return v.astype(dtype)
        return v
    return jax.tree_util.tree_map(cast, tree)


class ShardedTrainStep:
    """One compiled (fwd+bwd+optimizer) step over a device mesh.

    Parameters
    ----------
    block : gluon.HybridBlock (or a PureBlock)
    optimizer : str or FunctionalOptimizer ('sgd'/'adam')
    mesh : jax.sharding.Mesh (default: all devices on 'dp')
    loss_fn : callable(outputs:list[jax.Array], labels) -> scalar
    rules : ShardingRules for parameters (default: replicate)
    batch_axis / seq_axis : which input dims shard over 'dp' / 'sp'
    donate : donate param/state buffers (in-place update, the XLA
        analog of the reference's in-place optimizer kernels)
    compute_dtype : if set (e.g. jnp.bfloat16), the forward+backward
        runs in this dtype while fp32 master params receive the
        update — the reference's multi_precision / mp_sgd path (ref:
        src/operator/optimizer_op.cc MP_SGD), laid out TPU-style so
        the MXU sees bf16 operands.
    grad_accum : >1 splits the global batch into that many
        micro-batches inside ONE compiled step (lax.scan over grads),
        for effective batch sizes past the per-step memory budget.
        Global batch must be divisible by grad_accum (and the
        micro-batch by the 'dp' size).
    remat : rematerialize the forward during backward
        (jax.checkpoint) — activations recomputed, not stored.
    lr_schedule : callable(step:int32 tracer) -> lr, evaluated INSIDE
        the compiled step (optim.warmup_cosine / warmup_linear, or
        any jnp-traceable function) — no per-step recompiles.
    """

    def __init__(self, block, optimizer="sgd", optimizer_params=None,
                 mesh=None, loss_fn=None, rules=None, batch_axis=0,
                 seq_axis=None, donate=True, example_args=None,
                 compute_dtype=None, grad_accum=1, remat=False,
                 lr_schedule=None, zero=False):
        if mesh is None:
            mesh = current_mesh()  # ambient mesh from use_mesh(...)
        self.mesh = mesh if mesh is not None else make_mesh()
        if isinstance(block, PureBlock):
            self.pure = block
        else:
            self.pure = functionalize(block,
                                      *(example_args or ()))
        self.loss_fn = loss_fn or _default_loss
        if isinstance(optimizer, str):
            self.opt = foptim.create(optimizer,
                                     **(optimizer_params or {}))
        else:
            self.opt = optimizer
        if rules is None:
            # model-parallel meshes get the default Megatron/expert
            # rules out of the box: sharding is a LAYOUT choice, never
            # a semantics change (XLA derives the collectives), so the
            # only wrong default on a tp/ep mesh is full replication —
            # it silently wastes the axes the user asked for
            from .sharding import tp_rules_for_dense_stacks
            if (self.mesh.shape.get("tp", 1) > 1
                    or self.mesh.shape.get("ep", 1) > 1):
                # hand-built meshes may define only some axes: rules
                # touching absent axes drop to replicated
                rules = tp_rules_for_dense_stacks().restrict_to_axes(
                    self.mesh.axis_names)
        self.rules = rules or ShardingRules()
        self.batch_axis = batch_axis
        self.seq_axis = seq_axis
        self._donate = donate
        self.compute_dtype = compute_dtype
        self.grad_accum = max(1, int(grad_accum))
        self.remat = bool(remat)
        self.lr_schedule = lr_schedule
        self.step_count = jnp.zeros((), jnp.int32)

        # -- lay out current values over the mesh --------------------
        pvals = self.pure.params()
        svals = self.pure.states()
        self.param_shardings = self.rules.shardings(self.mesh, pvals)
        # what the forward/backward math wants (pre-ZeRO layout)
        self._compute_shardings = dict(self.param_shardings)
        self.zero = bool(zero) and self.mesh.shape.get("dp", 1) > 1
        if self.zero:
            # ZeRO-1: fp32 master params — and, via zeros_like
            # inheritance, every optimizer-state moment — live
            # dp-sharded; each dp rank updates only its slice and
            # GSPMD inserts the reduce-scatter/all-gather pair.
            # Memory per chip: params + opt state shrink by dp.
            # Rule-sharded (tp) leaves keep their layout.
            dp = self.mesh.shape["dp"]

            def zshard(name, a):
                base = self.param_shardings[name]
                if base.spec != P():
                    return base
                for ax, d in enumerate(a.shape):
                    if d > 0 and d % dp == 0:
                        spec = [None] * a.ndim
                        spec[ax] = "dp"
                        return NamedSharding(self.mesh, P(*spec))
                return base

            self.param_shardings = {n: zshard(n, a)
                                    for n, a in pvals.items()}
        self.state_shardings = {
            n: NamedSharding(self.mesh, P()) for n in svals}
        self.params = _owned_put_tree(pvals, self.param_shardings)
        self.states = _owned_put_tree(svals, self.state_shardings)
        self.opt_state = self.opt.init(self.params)
        self._step = None
        self._eval = None
        self._calls = 0     # host-side count of __call__ (span field)
        # memory planner (docs/memory.md): the preflight gate's
        # accepted plan + the cached forward-liveness walk (both
        # bind-time artifacts — nothing here runs on the step path)
        self._mem_plan = None
        self._mem_liveness = None

    # ---------------------------------------------------------------- build
    def _input_sharding(self, ndim, is_label=False):
        seq = self.seq_axis
        if is_label or (seq is not None and ndim <= seq):
            seq = None
        return shard_batch(self.mesh, ndim, self.batch_axis, seq)

    def _build(self, x, y):
        pure, loss_fn, opt = self.pure, self.loss_fn, self.opt
        cdt = self.compute_dtype
        accum = int(self.grad_accum)
        apply = pure.apply
        if self.remat:
            # rematerialize the forward during backward: activations
            # are recomputed instead of stored, trading MXU FLOPs for
            # HBM — the jax.checkpoint lever the TPU memory budget
            # usually wants for long sequences / deep nets
            apply = jax.checkpoint(
                lambda p, s, xs, rng: pure.apply(
                    p, s, xs, rng, training=True))

        zero = self.zero
        compute_sh = self._compute_shardings

        def grad_of(params, states, xb, yb, rng):
            def lossf(p):
                xin = xb
                if cdt is not None:
                    p = _cast_floats(p, cdt)
                    xin = _cast_floats(xb, cdt)
                if zero:
                    # gather the dp-sharded masters back to the
                    # compute layout AFTER the low-precision cast, so
                    # the all-gather moves bf16 bytes, not fp32
                    p = jax.lax.with_sharding_constraint(
                        p, {n: compute_sh[n] for n in p})
                outs, new_states = apply(p, states, [xin], rng)
                return loss_fn(outs, yb), new_states
            return jax.value_and_grad(lossf, has_aux=True)(params)

        if accum > 1:
            if self.batch_axis != 0:
                raise ValueError(
                    "grad_accum > 1 requires batch_axis=0 (the "
                    "micro-batch split slices axis 0); move the "
                    "batch to axis 0 or accumulate manually")
            if x.shape[0] % accum != 0:
                raise ValueError(
                    f"global batch {x.shape[0]} is not divisible by "
                    f"grad_accum={accum}")

        sched = self.lr_schedule

        def step(params, states, opt_state, t, x, y, rng):
            if accum <= 1:
                (loss, new_states), grads = grad_of(
                    params, states, x, y, rng)
            else:
                # micro-batch scan: grads accumulate, aux states
                # (BN moving stats) thread through sequentially —
                # one compiled step regardless of accum factor
                xm = x.reshape((accum, x.shape[0] // accum)
                               + x.shape[1:])
                ym = y.reshape((accum, y.shape[0] // accum)
                               + y.shape[1:])
                rngs = jax.random.split(rng, accum)

                def micro(carry, xyr):
                    gsum, lsum, st = carry
                    xb, yb, r = xyr
                    (loss, new_st), g = grad_of(params, st, xb, yb, r)
                    gsum = jax.tree_util.tree_map(
                        lambda a, b: a + b, gsum, g)
                    return (gsum, lsum + loss, new_st), None

                zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
                (gsum, lsum, new_states), _ = jax.lax.scan(
                    micro, (zeros, jnp.zeros((), jnp.float32),
                            states), (xm, ym, rngs))
                grads = jax.tree_util.tree_map(
                    lambda g: g / accum, gsum)
                loss = lsum / accum
            lr = sched(t) if sched is not None else None
            new_params, new_opt = opt.update(params, grads, opt_state,
                                             lr=lr)
            return new_params, new_states, new_opt, t + 1, loss

        in_sh = (self.param_shardings, self.state_shardings,
                 None,  # opt state: inherit param sharding via init
                 None,  # step count
                 self._input_sharding(x.ndim),
                 self._input_sharding(y.ndim, is_label=True),
                 None)
        out_sh = (self.param_shardings, self.state_shardings,
                  None, None, NamedSharding(self.mesh, P()))
        donate = (0, 1, 2) if self._donate else ()
        return jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=donate)

    # ------------------------------------------------------- memory plan
    def _trace_liveness(self, x, y):
        """Abstract-shape walk of the forward loss (jaxpr_liveness) —
        the activation term of the memory plan.  Cached; traces once
        at preflight time, never on the step path."""
        if self._mem_liveness is not None:
            return
        from ..perf.memory_planner import jaxpr_liveness
        pure, loss_fn, cdt = self.pure, self.loss_fn, self.compute_dtype

        def fwd(p, s, xa, ya, rng):
            if cdt is not None:
                p = _cast_floats(p, cdt)
                xa = _cast_floats(xa, cdt)
            outs, _ = pure.apply(p, s, [xa], rng, training=True)
            return loss_fn(outs, ya)

        abst = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        with use_mesh(self.mesh):
            self._mem_liveness = jaxpr_liveness(
                fwd, jax.tree_util.tree_map(abst, self.params),
                jax.tree_util.tree_map(abst, self.states),
                abst(x), abst(y),
                jax.ShapeDtypeStruct((2,), jnp.uint32))

    def _memory_plan(self, remat, grad_accum):
        """Per-device MemoryPlan for this step at the given knobs:
        sharded param/optimizer slice bytes (ZeRO/tp aware) + the
        traced activation liveness."""
        from ..perf import memory_planner as mp
        params_b = mp.sharded_tree_bytes(
            self.params, self.param_shardings) \
            + mp.tree_bytes(self.states)
        return mp.plan_memory(
            liveness=self._mem_liveness,
            params_bytes=params_b,
            max_param_bytes=mp.max_leaf_bytes(
                self.params, self.param_shardings),
            optimizer_bytes=mp.sharded_tree_bytes(self.opt_state),
            grad_accum=grad_accum, remat=remat,
            donate=self._donate,
            batch_shards=int(self.mesh.shape.get("dp", 1)))

    def _preflight(self, x, y):
        """Consult the analytic HBM plan before the first compile;
        under MXTPU_MEM_POLICY=degrade a predicted overflow walks the
        ladder (remat -> next grad_accum divisor) and the step adopts
        the surviving knobs.  Planner failures on exotic blocks are
        non-fatal (the gate is a guard, not a dependency); a dry
        ladder's MemoryPlanError stays loud."""
        from ..perf.memory_planner import preflight
        from ..resilience import MemoryPlanError
        try:
            self._trace_liveness(x, y)
            res = preflight(
                lambda r, a: self._memory_plan(r, a),
                site="sharded_train_step",
                device=self.mesh.devices.flat[0],
                can_remat=True,
                batch_size=int(x.shape[0])
                if self.batch_axis == 0 else 0,
                remat=self.remat, grad_accum=self.grad_accum)
        except MemoryPlanError:
            raise
        except Exception:
            logging.getLogger("mxtpu.memory").debug(
                "memory preflight skipped (planning failed)",
                exc_info=True)
            return
        if res is not None:
            self.remat = res.remat
            self.grad_accum = res.grad_accum
            self._mem_plan = res.plan

    def _oom_rung(self, oom, x):
        """One runtime degrade rung after a real (or injected) OOM at
        compile/execute: enable remat, else bump grad_accum to the
        next batch divisor, then rebuild for the single retry.  A dry
        ladder re-raises the typed OomError.  MXTPU_MEM_POLICY=off
        opts out of automatic degrading entirely — the OomError
        stays loud."""
        from .. import tracing
        from ..perf.memory_planner import next_divisor
        from ..utils.env import get_env
        if str(get_env("MXTPU_MEM_POLICY")).lower() == "off":
            raise oom
        rung = None
        if not self.remat:
            self.remat, rung = True, "remat"
        elif self.batch_axis == 0:
            nxt = next_divisor(int(x.shape[0]), self.grad_accum)
            if nxt is not None:
                self.grad_accum, rung = nxt, f"grad_accum={nxt}"
        if rung is None:
            raise oom
        self._step = None   # rebuild with the new knobs
        telemetry.counter("oom_retries_total").inc()
        tracing.trace_event("mem_degrade", site="sharded_train_step",
                            rung=rung, cause="runtime_oom")
        logging.getLogger("mxtpu.memory").warning(
            "OOM at sharded_train_step: degrade ladder rung '%s', "
            "retrying once%s", rung,
            " (numerics change: smaller micro-batches)"
            if rung.startswith("grad_accum") else
            " (numerics unchanged; more compute)")

    # ---------------------------------------------------------------- run
    def __call__(self, x, y, rng=None):
        """Run one training step on a *global* batch; returns loss."""
        with telemetry.span("train_step", step=self._calls):
            self._calls += 1
            return self._call(x, y, rng)

    def _call(self, x, y, rng):
        from ..dist import elastic_probe
        elastic_probe()     # elastic:rank<N> injection (docs/elastic.md)
        x, y = _raw(x), _raw(y)
        if rng is None:
            from .. import random_state
            rng = random_state.next_key()
        from ..resilience import as_oom_error, check_oom
        for attempt in (0, 1):
            try:
                if self._step is None:
                    with telemetry.span("train_preflight"):
                        self._preflight(x, y)
                    with telemetry.span("train_build"):
                        self._step = self._build(x, y)
                # mem:oom injection point (docs/resilience.md); a
                # no-op single bool check without MXTPU_FAULT_SPEC
                check_oom("sharded_train_step")
                with telemetry.span("train_put"):
                    xs = jax.device_put(x, self._input_sharding(x.ndim))
                    ys = jax.device_put(
                        y, self._input_sharding(y.ndim, True))
                # run (and, on the first call, trace) with this
                # step's mesh ambient, so mesh-aware blocks (e.g.
                # ring attention) resolve the step's mesh even when
                # called outside use_mesh()
                with telemetry.span("train_dispatch"), \
                        use_mesh(self.mesh):
                    (self.params, self.states, self.opt_state,
                     self.step_count, loss) = self._step(
                        self.params, self.states, self.opt_state,
                        self.step_count, xs, ys, rng)
                break
            except Exception as exc:
                oom = as_oom_error(exc, "sharded_train_step",
                                   plan=self._mem_plan)
                if oom is None:
                    raise
                if attempt:
                    raise oom from exc
                self._oom_rung(oom, x)   # raises when the ladder is dry
        return loss

    step = __call__

    def lowered(self, x, y):
        """This train step lowered (``jax.stages.Lowered``) for a
        global batch shaped like ``x``/``y``, against the step's real
        shardings: ``.as_text()`` shows what was staged out (a Pallas
        kernel is a ``tpu_custom_call``), ``.compile()`` gives XLA's
        executable with its ``as_text()`` (collectives included) and
        ``memory_analysis()``.  Lowers from abstract shapes; no data
        moves and nothing executes (note: an AOT compile does not
        seed the jit cache — the first real step() still traces)."""
        x, y = _raw(x), _raw(y)
        if self._step is None:
            self._step = self._build(x, y)
        # avals only: lowering never touches values, so don't pay a
        # host->device copy of a global batch just to ask a question
        xa = jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=self._input_sharding(x.ndim))
        ya = jax.ShapeDtypeStruct(
            y.shape, y.dtype,
            sharding=self._input_sharding(y.ndim, True))
        rng = jax.random.PRNGKey(0)   # traced arg; value irrelevant
        with use_mesh(self.mesh):
            return self._step.lower(
                self.params, self.states, self.opt_state,
                self.step_count, xa, ya, rng)

    def memory_analysis(self, x, y):
        """XLA's compiled-buffer accounting for this train step (the
        reference's memonger/`mirror` cost question: how much HBM
        does one step hold?).  Returns the backend's MemoryAnalysis
        (``.temp_size_in_bytes`` = activations + scratch) or None
        when the backend doesn't report one."""
        compiled = self.lowered(x, y).compile()
        try:
            return compiled.memory_analysis()
        except Exception:   # oom-ok: probing an optional backend API
            return None

    def evaluate(self, x, rng=None):
        """Compiled inference forward on a global batch."""
        x = _raw(x)
        if rng is None:
            from .. import random_state
            rng = random_state.next_key()
        if self._eval is None:
            pure = self.pure

            def ev(params, states, x, rng):
                outs, _ = pure.apply(params, states, [x], rng,
                                     training=False)
                return outs
            self._eval = jax.jit(ev)
        x = jax.device_put(x, self._input_sharding(x.ndim))
        with use_mesh(self.mesh):
            return self._eval(self.params, self.states, x, rng)

    def write_back(self):
        """Copy mesh values back into the Gluon Parameter objects.

        Hands the Parameters *owned copies*, never the step's own
        buffers — those are donated by the next step() and would turn
        the live Parameters into deleted arrays.
        """
        self.pure.write_back(_copy_tree(self.params),
                            _copy_tree(self.states))

    # ---------------------------------------------------------- checkpoint
    def save_checkpoint(self, path, data_state=None):
        """Write params + states + optimizer state to ``path`` (a
        checkpoint directory) in the native sharded-manifest format
        (parallel/checkpoint.py, docs/elastic.md): each rank writes
        only the slices it owns, a rank-0 manifest records the
        layout, and generations accumulate under the directory with
        corrupt-shard fallback on load.  ``data_state`` (an input
        iterator's ``state_dict()``) rides in the same generation so
        params and data cursors always travel together.  Values are
        copied first so the next step's buffer donation cannot race
        the write.  Returns the generation directory written."""
        from . import checkpoint as _ckpt
        return _ckpt.save_sharded(
            path, self._ckpt_tree(), self.mesh,
            step=int(self.step_count), data_state=data_state,
            extra={"optimizer": foptim.state_structure(
                self.opt_state)})

    def load_checkpoint(self, path):
        """Restore the newest valid generation under ``path`` INTO
        this step's mesh layout: every leaf is reassembled from the
        source slices that overlap this step's own shards, so resume
        works on a different mesh shape / world size than the save
        ran on (shrink and grow included).  Returns the loaded
        generation's data-iterator companion state (or None)."""
        from . import checkpoint as _ckpt
        tree = {"params": self.params, "states": self.states,
                "opt_state": self.opt_state,
                "step_count": self.step_count}
        restored, manifest, gen_dir = _ckpt.load_latest(
            path, tree, self.mesh)
        self.params = restored["params"]
        self.states = restored["states"]
        self.opt_state = restored["opt_state"]
        self.step_count = restored["step_count"]
        return _ckpt.load_data_companion(gen_dir, manifest)

    def _ckpt_tree(self):
        # generic pytree copy (opt_state nests beyond a flat dict)
        return _copy_tree({"params": self.params,
                           "states": self.states,
                           "opt_state": self.opt_state,
                           "step_count": self.step_count})


def _raw(a):
    from ..ndarray.ndarray import NDArray
    return a._data if isinstance(a, NDArray) else jnp.asarray(a)


def _owned_put_tree(vals, shardings):
    """Lay ``vals`` out per ``shardings`` in buffers this step *owns*.

    ``jax.device_put`` can hand back the input's own buffer when the
    value already lives on a target device — also as one shard of a
    mesh-wide result, and there even under ``may_alias=False`` (jax
    0.9.0, seen through ``unsafe_buffer_pointer``).  Donating such a
    result in the compiled step would delete the caller's array: the
    live gluon Parameter, or a sibling ShardedTrainStep built on the
    same block.  One compiled copy over the whole tree yields buffers
    nothing else holds.
    """
    placed = {n: jax.device_put(v, shardings[n])
              for n, v in vals.items()}
    if not placed:
        return placed
    return jax.jit(_copy_impl, out_shardings=shardings)(placed)


def _copy_impl(t):
    return jax.tree_util.tree_map(
        lambda a: a + jnp.zeros((), a.dtype), t)


# module-level fn so jax's jit cache is keyed on shapes/shardings and
# repeat constructions / write_backs hit the cache instead of
# re-tracing a fresh lambda every time
_copy_tree = jax.jit(_copy_impl)
