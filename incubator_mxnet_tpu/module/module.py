"""Module: concrete symbolic trainer over one compiled Executor
(ref: python/mxnet/module/module.py:  bind:355, init_params,
init_optimizer:464, forward:560, backward:602, update:619,
update_metric:726).

TPU-native note: the reference slices each batch across GPUs with
DataParallelExecutorGroup (ref: executor_group.py:99); here a single
Executor compiles the whole graph and data parallelism is expressed
with sharded batch arrays over the device mesh (parallel package), so
the "group" collapses to one executor whose inputs may be sharded.
"""
import logging

from .. import initializer as init_mod
from .. import optimizer as opt_mod
from .. import telemetry
from ..initializer import InitDesc
from ..model import (_create_kvstore, save_checkpoint,
                     load_checkpoint, checkpoint_companion_path,
                     save_data_state, load_data_state)
from ..ndarray.ndarray import NDArray
from .base_module import BaseModule

__all__ = ["Module"]


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None):
        super().__init__(logger)
        self._symbol = symbol
        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._context = context
        self._fixed_param_names = set(fixed_param_names or [])
        arg_names = symbol.list_arguments()
        self._param_names = [n for n in arg_names
                             if n not in self._data_names
                             and n not in self._label_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._exec = None
        self._optimizer = None
        self._updater = None
        self._guard = None       # step sentinel (MXTPU_NONFINITE_POLICY)
        self._kvstore = None
        self._update_on_kvstore = False
        self._data_shapes = None
        self._label_shapes = None
        self._mesh_step = None   # kvstore='tpu' fused path
        self._mesh_dirty = False    # step params newer than exec dicts
        self._mesh_pending = False  # fused step ran; update() owes a no-op
        self._mesh_stale = False    # exec dicts newer than step params
        self._perf_cost = None      # cached graph CostReport (3x fwd)

    # ------------------------------------------------------------ bind
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._symbol.list_outputs()

    @property
    def data_shapes(self):
        return self._data_shapes

    @property
    def label_shapes(self):
        return self._label_shapes

    @property
    def output_shapes(self):
        return list(zip(self.output_names, self._exec.output_shapes))

    @property
    def graph_opt_report(self):
        """Pass-pipeline report of the bound executor (per-pass node
        deltas; docs/graph_passes.md).  None before bind."""
        return getattr(self, "_graph_opt_report", None)

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False,
             shared_module=None, grad_req="write"):
        if self.binded and not force_rebind:
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._data_shapes = [d if hasattr(d, "name") else
                             _to_desc(d) for d in data_shapes]
        self._label_shapes = [d if hasattr(d, "name") else _to_desc(d)
                              for d in (label_shapes or [])]
        shapes = {d.name: d.shape for d in self._data_shapes}
        shapes.update({d.name: d.shape for d in self._label_shapes})
        if isinstance(grad_req, str):
            req = {}
            for n in self._symbol.list_arguments():
                if n in self._fixed_param_names or (
                        not for_training) or (
                        n in self._data_names and not inputs_need_grad
                ) or n in self._label_names:
                    req[n] = "null"
                else:
                    req[n] = grad_req
        else:
            req = grad_req
        self._preflight_memory(shapes, for_training)
        self._exec = self._symbol.simple_bind(
            self._context, grad_req=req, **shapes)
        # pass-pipeline outcome of this bind (docs/graph_passes.md):
        # per-pass node deltas, None when MXTPU_GRAPH_OPT=0 or placed
        self._graph_opt_report = self._exec.graph_report
        if shared_module is not None and shared_module._exec is not None:
            self._exec.copy_params_from(
                shared_module._exec.arg_dict,
                shared_module._exec.aux_dict, allow_extra_params=True)
        self.binded = True
        # Module.load path: apply checkpointed params on first bind
        # (ref: module.py Module.load sets _arg_params + initialized)
        if getattr(self, "_preloaded_params", None) is not None:
            arg, aux = self._preloaded_params
            self.init_params(arg_params=arg, aux_params=aux,
                             force_init=True)
            self._preloaded_params = None

    def _preflight_memory(self, shapes, for_training):
        """Analytic HBM gate at bind time (docs/memory.md): plan the
        executor's peak live bytes (eager grads, no donation) against
        device capacity per MXTPU_MEM_POLICY.  The single-executor
        path has no remat/grad_accum rungs, so the ladder is empty —
        the plan fits, warns, or raises a typed MemoryPlanError
        before any compile.  Planner failures on exotic graphs are
        non-fatal; the gate is a guard, not a dependency."""
        from ..perf import memory_planner as mp
        from ..resilience import MemoryPlanError
        try:
            live = mp.symbol_liveness(self._symbol, dict(shapes),
                                      input_names=list(shapes))
            mp.preflight(
                lambda r, a: mp.plan_memory(
                    liveness=live, train=for_training,
                    donate=False, grad_accum=a, remat=r),
                site="module_bind")
        except MemoryPlanError:
            raise
        except Exception:
            self.logger.debug(
                "memory preflight skipped (planning failed)",
                exc_info=True)

    # ------------------------------------------------------------ params
    def init_params(self, initializer=None, arg_params=None,
                    aux_params=None, allow_missing=False,
                    force_init=False, allow_extra=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before init_params"
        attrs = self._symbol.attr_dict()

        def _fill(name, arr, cache):
            if cache is not None and name in cache:
                arr[:] = cache[name]
                return
            if cache is not None and not allow_missing:
                raise RuntimeError(
                    f"parameter '{name}' missing from provided params "
                    "(pass allow_missing=True to initialize it)")
            if initializer is not None:
                initializer(InitDesc(name, attrs.get(name, {})), arr)
            elif cache is None:
                init_mod.Uniform(0.01)(
                    InitDesc(name, attrs.get(name, {})), arr)

        for name in self._param_names:
            _fill(name, self._exec.arg_dict[name], arg_params)
        for name in self._aux_names:
            _fill(name, self._exec.aux_dict[name], aux_params)
        if self._mesh_step is not None:
            # the exec dicts are now the source of truth (set_params
            # mid-training, divergence rollback): the mesh step must
            # re-pull them before its next fused step, and a pending
            # sync from the step must not clobber them
            self._mesh_dirty = False
            self._mesh_stale = True
        self.params_initialized = True

    def get_params(self):
        self._sync_mesh_params()
        arg = {n: self._exec.arg_dict[n].copy()
               for n in self._param_names}
        aux = {n: self._exec.aux_dict[n].copy()
               for n in self._aux_names}
        return arg, aux

    # ------------------------------------------------------------ optimizer
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            return
        arg_params = {n: self._exec.arg_dict[n]
                      for n in self._param_names}
        use_mesh_step = (isinstance(kvstore, str) and kvstore == "tpu")
        kv, update_on_kvstore = (None, False) if use_mesh_step else \
            _create_kvstore(kvstore, 1, arg_params)
        if isinstance(optimizer, str):
            params = dict(optimizer_params or ())
            # reference default: scale summed grads by 1/batch_size
            # (ref: module.py init_optimizer:464 rescale_grad); on a
            # multi-process mesh the global batch is num_workers larger
            if "rescale_grad" not in params and self._data_shapes:
                batch_size = self._data_shapes[0].shape[0]
                if kv is not None and kv.num_workers > 1:
                    batch_size *= kv.num_workers
                params["rescale_grad"] = 1.0 / max(batch_size, 1)
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            optimizer = opt_mod.create(
                optimizer, sym=self._symbol, param_idx2name=idx2name,
                **params)
        self._optimizer = optimizer
        self._kvstore = kv
        self._update_on_kvstore = update_on_kvstore and kv is not None
        self._updater = None
        # step sentinel (docs/numeric_stability.md): armed by
        # MXTPU_NONFINITE_POLICY; the Module path has no user-scaled
        # loss, so no LossScaler here (that is the gluon Trainer's)
        from ..resilience import NumericGuard
        guard = NumericGuard(name="Module")
        self._guard = guard if guard.enabled else None
        if use_mesh_step:
            self._init_mesh_step()
        if kv is not None:
            for i, name in enumerate(self._param_names):
                kv.init(i, self._exec.arg_dict[name])
            if self._update_on_kvstore:
                kv.set_optimizer(self._optimizer)
        if not self._update_on_kvstore and not use_mesh_step:
            self._updater = opt_mod.GuardedUpdater(
                optimizer, guard=self._guard) \
                if self._guard is not None \
                else opt_mod.get_updater(optimizer)
        if not use_mesh_step:
            # device-memory attribution (docs/observability.md); the
            # mesh path's SymbolTrainStep registers its own providers
            self._register_memory_providers()
        self.optimizer_initialized = True
        states = getattr(self, "_preload_opt_states", None)
        if states:
            from ..resilience import CheckpointCorruptError
            try:
                self.load_optimizer_states(states)
            except (FileNotFoundError, CheckpointCorruptError) as exc:
                # the params may have come from a fallback epoch
                # whose .states never existed or was torn; resume
                # with fresh optimizer state rather than crash:
                # weights are intact, momentum rebuilds.  Other
                # OSErrors (EACCES, transient NFS faults) stay loud —
                # the state likely exists and dropping it would
                # silently degrade convergence
                import warnings
                warnings.warn(
                    f"optimizer states {states} could not be loaded "
                    f"({exc}); resuming with freshly initialized "
                    "optimizer state", RuntimeWarning)
            self._preload_opt_states = None

    def _register_memory_providers(self):
        """Attribute this module's device buffers in the tracing
        layer's memory gauges: bound params + eager-updater optimizer
        state.  Weakref'd so a dropped module stops being counted;
        idempotent per init_optimizer (providers re-register on
        force_init, superseding via the old module's weakref dying
        with it)."""
        from .. import tracing
        for unreg in getattr(self, "_mem_unregister", ()):
            unreg()

        def _param_arrays(mod):
            if mod._exec is None:
                return []
            return [mod._exec.arg_dict[n]._data
                    for n in mod._param_names
                    if n in mod._exec.arg_dict]

        def _opt_arrays(mod):
            states = getattr(mod._updater, "states", None)
            return tracing.updater_state_arrays(states) \
                if states else []

        self._mem_unregister = tracing.register_param_opt_providers(
            self, _param_arrays, _opt_arrays)

    # ------------------------------------------------------------ mesh
    def _init_mesh_step(self):
        """kvstore='tpu': build the fused mesh training step.

        Replaces DataParallelExecutorGroup batch slicing + kvstore
        push/pull (ref: python/mxnet/module/executor_group.py:99) with
        one jit step over the ambient mesh: batch sharded on 'dp',
        grads psum'd by XLA, functional optimizer applied in-jit.
        """
        from ..parallel import current_mesh, make_mesh
        from ..parallel.symbol_step import SymbolTrainStep
        opt = self._optimizer
        fopt = _to_functional_optimizer(opt)
        if fopt is None:
            raise ValueError(
                f"kvstore='tpu' supports sgd/nag/adam-family "
                f"optimizers in the fused step; got "
                f"{type(opt).__name__}. Use kvstore='device' for the "
                "eager update path.")
        trainable = [n for n in self._param_names
                     if n in self._exec.grad_dict]
        pvals = {n: self._exec.arg_dict[n]._data for n in trainable}
        # fixed params + aux states ride in the aux (constant) slot
        aux_vals = {n: self._exec.aux_dict[n]._data
                    for n in self._aux_names}
        aux_vals.update({n: self._exec.arg_dict[n]._data
                         for n in self._param_names
                         if n not in self._exec.grad_dict})
        input_names = [d.name for d in self._data_shapes]
        input_names += [d.name for d in (self._label_shapes or [])
                        if d.name in self._exec.arg_dict]
        from ..parallel.optim import default_wd_mults
        wd_mults = default_wd_mults(trainable, opt.wd_mult)
        lr_mults = {n: opt.lr_mult.get(n, 1.0) for n in trainable}
        mesh = current_mesh() or make_mesh()
        self._mesh_step = SymbolTrainStep(
            self._symbol, pvals, aux_vals, input_names,
            optimizer=fopt, mesh=mesh,
            rescale_grad=getattr(opt, "rescale_grad", 1.0),
            lr_mults=lr_mults, wd_mults=wd_mults,
            numeric_guard=self._guard is not None,
            guard_select=self._guard is not None
            and self._guard.drops_updates)

    def _sync_mesh_params(self):
        """Pull owned copies from the mesh step back into the
        executor dicts (lazy: only when values are actually read)."""
        if self._mesh_step is None or not self._mesh_dirty:
            return
        params, aux = self._mesh_step.owned_values()
        for n, v in params.items():
            self._exec.arg_dict[n]._data = v
        for n, v in aux.items():
            if n in self._exec.aux_dict:
                self._exec.aux_dict[n]._data = v
            else:  # fixed params rode in the aux slot
                self._exec.arg_dict[n]._data = v
        self._mesh_dirty = False

    # ------------------------------------------------------------ step
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        inputs = self._batch_inputs(data_batch)
        if not is_train and self._mesh_step is not None \
                and not self._mesh_stale:
            vals = {k: (v._data if isinstance(v, NDArray) else v)
                    for k, v in inputs.items()}
            need = self._mesh_step.input_names
            dp = self._mesh_step.mesh.shape["dp"]
            batches = [vals[n].shape[0] for n in need if n in vals]
            if set(need) <= set(vals) and \
                    all(b % dp == 0 for b in batches):
                # compiled sharded eval over the mesh (score/predict)
                outs = self._mesh_step.evaluate(
                    {n: vals[n] for n in need})
                self._exec._outputs = [NDArray(o) for o in outs]
                return
        self._sync_mesh_params()
        self._exec.forward(is_train=is_train, **inputs)

    def _batch_inputs(self, data_batch):
        inputs = {}
        bound = self._exec.arg_dict
        for desc, arr in zip(self._data_shapes, data_batch.data):
            inputs[desc.name] = arr
        if data_batch.label is not None and self._label_shapes:
            for desc, arr in zip(self._label_shapes, data_batch.label):
                if desc.name in bound:  # symbol may be label-free
                    inputs[desc.name] = arr
        return inputs

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    def forward_backward(self, data_batch):
        """Fused single-XLA-call training step (outputs + grads)."""
        if self._mesh_step is not None:
            from ..parallel.optim import scheduled_lr
            if self._mesh_stale:
                # an eager update touched the exec dicts; refresh the
                # step's device values before continuing fused
                self._push_mesh_params()
            inputs = {k: v._data if isinstance(v, NDArray) else v
                      for k, v in self._batch_inputs(data_batch).items()}
            outs = self._mesh_step(inputs,
                                   lr=scheduled_lr(self._optimizer))
            self._exec._outputs = [NDArray(o) for o in outs]
            self._mesh_dirty = True
            self._mesh_pending = True
            return
        self._exec.forward_backward(**self._batch_inputs(data_batch))

    def _push_mesh_params(self):
        trainable = {n: self._exec.arg_dict[n]._data
                     for n in self._mesh_step.params}
        aux = {n: (self._exec.aux_dict[n]._data
                   if n in self._exec.aux_dict
                   else self._exec.arg_dict[n]._data)
               for n in self._mesh_step.aux}
        self._mesh_step.set_values(trainable, aux)
        self._mesh_stale = False

    def update(self):
        """(ref: module.py update:619 / model.py
        _update_params_on_kvstore:105)

        Step sentinel (docs/numeric_stability.md): with
        MXTPU_NONFINITE_POLICY armed, the step's gradients reduce to
        one fused finiteness scalar, host-read every
        MXTPU_GUARD_INTERVAL steps; a bad step is skipped whole
        (weights, optimizer state, LR-schedule count), and
        MXTPU_MAX_BAD_STEPS consecutive bad steps raise
        DivergedError for fit's checkpoint rollback."""
        assert self.optimizer_initialized
        telemetry.counter("train_steps_total").inc()
        if self._mesh_step is not None:
            if self._mesh_pending:
                # the optimizer already ran inside the fused mesh
                # step; the guarded build protected params/state on
                # device (in-jit select) — the host only consumes
                # the flag on due steps for policy and divergence
                # accounting
                self._mesh_pending = False
                if self._guard is not None:
                    due = self._guard.begin_step()
                    opt_mod.accumulate_window(
                        self._guard, self._mesh_step.last_finite)
                    if due:
                        # the one guard-interval device->host read —
                        # the 'host_sync' slice of the step timeline
                        with telemetry.span("host_sync"):
                            bad = opt_mod.read_window_bad(
                                self._guard)
                        if bad and self._guard.drops_updates:
                            # those updates were dropped on device;
                            # keep the LR schedule in step with the
                            # weights (exact count, before record —
                            # policy=raise raises there)
                            self._optimizer.num_update -= bad
                        self._guard.record(bad == 0,
                                           dropped=max(bad, 1))
                return
            # manual forward/backward loop with kvstore='tpu': apply
            # the eager updater so update() is never a silent no-op
            if self._updater is None:
                self._updater = opt_mod.GuardedUpdater(
                    self._optimizer, guard=self._guard) \
                    if self._guard is not None \
                    else opt_mod.get_updater(self._optimizer)
            self._sync_mesh_params()
            self._mesh_stale = True
        if self._guard is not None:
            grads = [g for g in
                     (self._exec.grad_dict.get(n)
                      for n in self._param_names) if g is not None]
            if isinstance(self._updater, opt_mod.GuardedUpdater):
                proceed = self._updater.begin_step(grads)
            else:
                # update_on_kvstore: the optimizer runs inside the
                # kvstore, so guard the step before any push — the
                # skip must also cover the collectives (rank-
                # consistent via the allreduced flag)
                proceed = opt_mod.guarded_step_begin(
                    self._guard, None, grads)
            if not proceed:
                return
        for i, name in enumerate(self._param_names):
            grad = self._exec.grad_dict.get(name)
            if grad is None:  # fixed / grad_req=null parameters
                continue
            kv = self._kvstore
            if kv is not None and self._update_on_kvstore:
                kv.push(i, grad, priority=-i)
                kv.pull(i, out=self._exec.arg_dict[name], priority=-i)
            elif kv is not None:
                kv.push(i, grad, priority=-i)
                kv.pull(i, out=grad, priority=-i)
                self._updater(i, grad, self._exec.arg_dict[name])
            else:
                self._updater(i, grad, self._exec.arg_dict[name])

    # ------------------------------------------------------------ perf
    def _bound_shapes(self):
        """Variable name -> shape for everything the bind fixed."""
        shapes = {d.name: tuple(d.shape) for d in self._data_shapes}
        shapes.update({d.name: tuple(d.shape)
                       for d in (self._label_shapes or [])})
        for n in self._param_names:
            shapes[n] = tuple(self._exec.arg_dict[n].shape)
        for n in self._aux_names:
            shapes[n] = tuple(self._exec.aux_dict[n].shape)
        return shapes

    def _graph_cost(self):
        """Analytic CostReport of one TRAIN step (3x forward) at the
        bound shapes; cached per bind."""
        if self._perf_cost is None:
            from .. import perf
            self._perf_cost = perf.symbol_cost(
                self._symbol, self._bound_shapes()).scaled(3.0)
        return self._perf_cost

    def perf_report(self, xla_check=True):
        """Per-family cost/roofline report for the bound graph
        (docs/observability.md "Perf observatory").

        Returns a dict: ``per_family`` rows (flops%, bytes%,
        predicted-time%, bound-by label, arithmetic intensity),
        ``total`` summary, coverage counts, the device roofline
        verdict for one train step, and — when the backend reports
        ``cost_analysis()`` — the analytic-vs-XLA forward-FLOPs
        delta."""
        assert self.binded, "call bind before perf_report"
        import jax

        from .. import perf
        rep = self._graph_cost()
        dev = jax.devices()[0]
        caps = perf.caps_for(dev)
        dtype = str(next(iter(self._exec.arg_dict.values())).dtype) \
            if self._exec.arg_dict else "float32"
        out = {
            "per_family": rep.table(caps, dtype),
            "total": rep.summary(),
            "coverage": rep.coverage,
            "default_ops": rep.default_ops,
            "unknown_ops": rep.unknown_ops,
            "roofline": perf.roofline(rep.flops, rep.bytes, caps,
                                      dtype),
            "device": caps.as_dict(),
            "n_nodes": rep.n_nodes,
        }
        if xla_check:
            out["xla_check"] = self._xla_fwd_delta(rep)
        return out

    def _xla_fwd_delta(self, train_rep):
        """Analytic-vs-XLA forward FLOPs delta via the executor's
        compiled forward (AOT lowering; nothing executes).  None
        when the backend doesn't report cost_analysis()."""
        import jax

        from .. import perf
        try:
            fwd = self._exec._get_fwd(False)
            args = {n: jax.ShapeDtypeStruct(tuple(v.shape),
                                            v.dtype)
                    for n, v in self._exec.arg_dict.items()}
            auxs = {n: jax.ShapeDtypeStruct(tuple(v.shape),
                                            v.dtype)
                    for n, v in self._exec.aux_dict.items()}
            import numpy as np
            rng = jax.ShapeDtypeStruct((2,), np.dtype("uint32"))
            xc = perf.jit_cost(fwd, args, auxs, rng)
        except Exception:
            return None
        if not xc or not xc.get("flops"):
            return None
        analytic_fwd = train_rep.flops / 3.0
        return {"analytic_fwd_flops": analytic_fwd,
                "xla_fwd_flops": xc["flops"],
                "rel_delta": abs(analytic_fwd - xc["flops"])
                / xc["flops"]}

    def get_outputs(self, merge_multi_context=True):
        return self._exec.outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        return [self._exec.grad_dict[n] for n in self._data_names]

    def update_metric(self, eval_metric, labels):
        eval_metric.update(labels, self._exec.outputs)

    def install_monitor(self, mon):
        mon.install(self._exec)

    # ------------------------------------------------------------ ckpt
    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        data_iter=None):
        """Save params (+ optimizer states, + input-pipeline position
        when ``data_iter`` is given) — every file atomically, so the
        launcher's restart loop always finds a coherent set."""
        arg, aux = self.get_params()
        save_checkpoint(prefix, epoch, self._symbol, arg, aux)
        if save_optimizer_states:
            self.save_optimizer_states(f"{prefix}-{epoch:04d}.states")
        if data_iter is not None:
            save_data_state(prefix, epoch, data_iter)

    @staticmethod
    def load_data_state(prefix, epoch, data_iter, strict=False):
        """Restore ``data_iter`` from the checkpoint's ``.data``
        companion (see ``model.load_data_state``): the resumed stream
        continues at the exact batch the checkpoint was taken at."""
        return load_data_state(prefix, epoch, data_iter,
                               strict=strict)

    # ----------------------------------------------------- elastic ckpt
    def save_sharded_checkpoint(self, ckpt_dir, step=None,
                                data_iter=None):
        """Elastic sharded checkpoint (docs/elastic.md): params +
        aux + in-jit optimizer state land as one manifest generation
        under ``ckpt_dir``, each rank writing only the slices it
        owns; the input pipeline's position rides in the same
        generation when ``data_iter`` is given.  kvstore='tpu' mesh
        path only — the eager paths keep the legacy
        prefix/epoch format.  Returns the generation directory."""
        if self._mesh_step is None:
            raise RuntimeError(
                "save_sharded_checkpoint needs the kvstore='tpu' "
                "mesh step (legacy contexts: use save_checkpoint)")
        if self._mesh_stale:
            # an eager update / set_params touched the exec dicts
            # since the last fused step: checkpoint what the user
            # sees, not the step's pre-update device values
            self._push_mesh_params()
        data_state = data_iter.state_dict() \
            if data_iter is not None else None
        return self._mesh_step.save_checkpoint(
            ckpt_dir, step=step, data_state=data_state)

    def load_sharded_checkpoint(self, ckpt_dir, data_iter=None):
        """Restore the newest valid sharded generation into the mesh
        step — resharded onto THIS job's mesh, which need not match
        the saving job's shape or world size — and re-shard the data
        iterator's cursors from the generation's companion when
        ``data_iter`` is given.  Returns the companion state (or
        None)."""
        if self._mesh_step is None:
            raise RuntimeError(
                "load_sharded_checkpoint needs the kvstore='tpu' "
                "mesh step (legacy contexts: use model."
                "load_checkpoint)")
        state = self._mesh_step.load_checkpoint(ckpt_dir)
        # restored values are now the source of truth: exec dicts
        # must re-pull them, and no stale push may clobber them
        self._mesh_dirty = True
        self._mesh_stale = False
        if data_iter is not None and state is not None:
            data_iter.load_state_dict(state)
        return state

    def save_optimizer_states(self, fname):
        from .. import resilience
        assert self.optimizer_initialized
        if self._mesh_step is not None:
            import pickle
            import numpy as _np
            import jax as _jax
            tree = _jax.tree_util.tree_map(_np.asarray,
                                           self._mesh_step.opt_state)
            resilience.atomic_save(
                fname, lambda f: pickle.dump(tree, f))
        elif self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            resilience.atomic_write_bytes(
                fname, self._updater.get_states())

    def load_optimizer_states(self, fname):
        from .. import resilience
        assert self.optimizer_initialized
        if self._mesh_step is not None:
            import pickle
            import jax as _jax
            import jax.numpy as _jnp
            raw = resilience.read_validated_bytes(fname)
            tree = resilience.decode_or_corrupt(
                fname, lambda: pickle.loads(raw))
            self._mesh_step.opt_state = _jax.tree_util.tree_map(
                _jnp.asarray, tree)
        elif self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            import pickle
            raw = resilience.read_validated_bytes(fname)
            # decode under the corruption guard, apply outside it
            obj = resilience.decode_or_corrupt(
                fname, lambda: pickle.loads(raw))
            self._updater.set_states(obj)

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Load a checkpointed Module; params apply automatically on
        bind() (ref: module.py Module.load)."""
        symbol, arg_params, aux_params, eff = load_checkpoint(
            prefix, epoch, return_epoch=True)
        mod = Module(symbol, **kwargs)
        mod._preloaded_params = (arg_params, aux_params)
        # pair optimizer state with the checkpoint that actually
        # loaded — a corrupt-load fallback may have substituted an
        # earlier one, possibly under an unpadded filename
        mod._preload_opt_states = \
            checkpoint_companion_path(prefix, eff) \
            if load_optimizer_states else None
        return mod


def _to_desc(d):
    from ..io.io import DataDesc
    name, shape = d
    return DataDesc(name, shape)


def _to_functional_optimizer(opt):
    from ..parallel.optim import from_imperative
    return from_imperative(opt)
