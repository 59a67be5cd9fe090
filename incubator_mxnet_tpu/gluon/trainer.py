"""Gluon Trainer (ref: python/mxnet/gluon/trainer.py — _init_kvstore:102,
step pushes grads / pulls weights per parameter).

TPU-native: the default hot path is a *fused in-jit update* — one
compiled call applying the optimizer to the whole parameter pytree
(with the reference's per-parameter lr_mult/wd_mult semantics as
multiplier trees), instead of the reference's per-parameter Python
loop of push/pull/updater calls.  Learning rate and grad rescale are
traced scalars, so schedulers run without recompiles.

With kvstore='tpu' gradients are already mesh-reduced inside the
compiled step that produced them (psum via sharding), so step() is
just the fused optimizer application; 'device'/'local' behave the
same on one process.  Optimizers without a functional counterpart
(see parallel.optim.from_imperative) fall back to the eager per-param
updater loop transparently.
"""

import jax
import jax.numpy as jnp

from .. import optimizer as opt_mod
from .. import telemetry
from .. import tracing
from ..model import _create_kvstore
from ..parallel import optim as foptim

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None):
        if isinstance(params, (dict,)) or hasattr(params, "values"):
            params = list(params.values())
        self._params = [p for p in params if p.grad_req != "null"]
        self._scale = 1.0
        optimizer_params = dict(optimizer_params or {})
        if isinstance(optimizer, str):
            idx2name = {i: p.name for i, p in enumerate(self._params)}
            self._optimizer = opt_mod.create(
                optimizer, param_idx2name=idx2name, **optimizer_params)
        else:
            self._optimizer = optimizer
        for i, p in enumerate(self._params):
            self._optimizer.set_lr_mult({p.name: p.lr_mult})
            self._optimizer.set_wd_mult({p.name: p.wd_mult})
        self._updater = opt_mod.get_updater(self._optimizer)
        # step sentinel (docs/numeric_stability.md): guard policy and
        # loss scaler come from the MXTPU_NONFINITE_POLICY /
        # MXTPU_LOSS_SCALE* env knobs; both default to inert
        from .. import resilience
        self._scaler = opt_mod.LossScaler()
        self._guard = resilience.NumericGuard(name="gluon.Trainer")
        telemetry.maybe_start_emitter()
        if self._scaler.dynamic and not self._guard.enabled:
            # dynamic loss scaling IS skip-on-overflow: the scaler's
            # overflow signal is the guard's finiteness flag, and an
            # overflow step must not reach the weights
            self._guard.policy = "skip"
        # device-memory attribution (docs/observability.md): weakref
        # providers so a dropped Trainer stops being counted
        def _param_arrays(tr):
            return [p._data._data for p in tr._params
                    if p._data is not None]

        def _opt_arrays(tr):
            leaves = []
            fstate = getattr(tr, "_fstate", None)
            if fstate is not None:
                leaves += jax.tree_util.tree_leaves(fstate)
            states = getattr(tr._updater, "states", None)
            if states:
                leaves += tracing.updater_state_arrays(states)
            return leaves

        self._mem_unregister = tracing.register_param_opt_providers(
            self, _param_arrays, _opt_arrays)
        self._kvstore_spec = kvstore
        self._kvstore = None
        self._kv_initialized = False
        self._fopt = None        # functional optimizer (fused path)
        self._fstate = None
        self._fused_update = None
        self._mesh = None
        if kvstore == "tpu":
            # capture the ambient mesh NOW: step() may run outside the
            # use_mesh() scope, and re-resolving there would replicate
            # params over a different device set than the gradients
            from ..parallel import current_mesh, make_mesh
            self._mesh = current_mesh() or make_mesh()
            # replicate now so the *first* forward on a 'dp'-sharded
            # batch already computes distributed (step() comes later)
            self._replicate_params()

    def _replicate_params(self):
        from ..parallel import replicated
        rep = replicated(self._mesh)
        for p in self._params:
            if p._data is not None:
                p._data._data = jax.device_put(p._data._data, rep)

    @property
    def learning_rate(self):
        return self._optimizer.lr

    @property
    def loss_scale(self):
        """Current loss scale — when loss scaling is enabled
        (MXTPU_LOSS_SCALE*), multiply the loss by this before
        ``backward()``; ``step()`` rescales the gradients back."""
        return self._scaler.scale

    @property
    def guard(self):
        """The step sentinel's NumericGuard (skip/bad-step counters,
        host-read accounting)."""
        return self._guard

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def _init_kvstore(self):
        """(ref: trainer.py:102)"""
        if self._kvstore_spec == "tpu":
            # mesh path: parameters replicated over the ambient mesh
            # (done in __init__, repeated here for deferred-init
            # parameters); grads were already mesh-reduced inside the
            # computation that produced them; no store object needed
            self._replicate_params()
            self._kvstore = None
            self._update_on_kvstore = False
        else:
            arg_params = {p.name: p.data() for p in self._params}
            kv, update_on_kvstore = _create_kvstore(
                self._kvstore_spec, 1, arg_params)
            self._kvstore = kv
            self._update_on_kvstore = update_on_kvstore and \
                kv is not None
            if kv is not None:
                for i, p in enumerate(self._params):
                    kv.init(i, p.data())
                if self._update_on_kvstore:
                    kv.set_optimizer(self._optimizer)
        self._kv_initialized = True

    # ---------------------------------------------------------- fused
    def _init_fused(self):
        """Resolve the functional optimizer for the one-jit-call
        whole-tree update (None counterpart -> eager loop)."""
        opt = self._optimizer
        self._fopt = foptim.from_imperative(opt)
        if self._fopt is None:
            self._fused_update = False  # sentinel: use eager loop
            return
        self._fused_update = {}  # per stale-grad-mask compiled variants
        self._fstate = self._fopt.init(
            {p.name: p.data()._data for p in self._params})

    def _fused_variant(self, missing_names, guarded=False,
                       select=False):
        """Compiled update skipping ``missing_names`` (stale grads):
        the reference leaves both weight and optimizer state of a
        grad-less parameter untouched, so the fused step restores
        those leaves after the whole-tree update.

        With ``guarded=True`` the executable additionally reduces
        the gradients to one finiteness scalar, returned as a third
        output for the guard's interval read.  ``select=True``
        (policies that drop bad updates — skip/raise) further routes
        the whole update through a ``where(finite, new, old)`` select
        so a bad step never reaches weights or optimizer state, on
        device, with zero host syncs; under policy=warn the select
        stays off — warn's contract is to apply the update anyway."""
        fn = self._fused_update.get((missing_names, guarded, select))
        if fn is not None:
            return fn
        opt, fopt = self._optimizer, self._fopt
        lr_mults = {p.name: opt.lr_mult.get(p.name, 1.0)
                    for p in self._params}
        wd_mults = foptim.default_wd_mults(
            [p.name for p in self._params], opt.wd_mult)

        def upd(params, grads, state, scale, lr):
            new_p, new_s = fopt.update(params, grads, state,
                                       scale=scale, lr=lr,
                                       lr_mults=lr_mults,
                                       wd_mults=wd_mults)
            if missing_names:
                new_p = dict(new_p)
                for n in missing_names:
                    new_p[n] = params[n]
                new_s = {k: ({**v, **{n: state[k][n]
                                      for n in missing_names if n in v}}
                             if isinstance(v, dict) else v)
                         for k, v in new_s.items()}
            if not guarded:
                return new_p, new_s
            finite = jnp.asarray(
                opt_mod.all_finite(list(grads.values())))
            if not select:
                return new_p, new_s, finite
            sel = lambda new, old: jax.tree_util.tree_map(
                lambda a, b: jnp.where(finite, a, b), new, old)
            return sel(new_p, params), sel(new_s, state), finite

        fn = jax.jit(upd, donate_argnums=(0, 2))
        self._fused_update[(missing_names, guarded, select)] = fn
        return fn

    def _fused_active(self):
        if self._fused_update in (None, False):
            return False
        kv = self._kvstore
        return not (kv is not None
                    and getattr(kv, "num_workers", 1) > 1)

    def step(self, batch_size, ignore_stale_grad=False):
        """Apply one optimizer step scaled by 1/batch_size
        (ref: trainer.py step).

        With the step sentinel on (MXTPU_NONFINITE_POLICY=skip, or
        dynamic loss scaling), a step whose gradients are non-finite
        is dropped whole: weights, optimizer state, and the
        LR-schedule step count stay untouched, and in multi-rank runs
        the skip decision is allreduced so every replica agrees."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._fused_update is None:
            self._init_fused()
        self._optimizer.rescale_grad = self._scale / batch_size
        if self._scaler.active:
            # gradients were computed on a loss multiplied by
            # self.loss_scale; scale them back in the same fused
            # rescale the batch-size division uses
            self._optimizer.rescale_grad /= self._scaler.scale

        missing = [p for p in self._params if p._grad is None]
        if missing and not ignore_stale_grad:
            raise UserWarning(
                f"Gradient of Parameter `{missing[0].name}` not set; "
                "call backward first, or set ignore_stale_grad=True")

        telemetry.counter("train_steps_total").inc()
        guarded = self._guard.enabled
        if self._fused_active():
            with telemetry.span("optimizer"):
                params = {p.name: p.data()._data
                          for p in self._params}
                grads = {p.name: (p._grad._data
                                  if p._grad is not None
                                  else jnp.zeros_like(p.data()._data))
                         for p in self._params}
                if guarded:
                    poison = opt_mod.grad_poison()
                    if poison is not None:
                        first = next(iter(grads))
                        grads[first] = grads[first] * poison
                fn = self._fused_variant(
                    tuple(sorted(p.name for p in missing)), guarded,
                    self._guard.drops_updates)
                out = fn(
                    params, grads, self._fstate,
                    jnp.asarray(self._optimizer.rescale_grad,
                                jnp.float32),
                    jnp.asarray(foptim.scheduled_lr(self._optimizer),
                                jnp.float32))
                if guarded:
                    new_p, self._fstate, flag = out
                else:
                    new_p, self._fstate = out
                for p in self._params:
                    p._data._data = new_p[p.name]
            if guarded:
                due = self._guard.begin_step()
                opt_mod.accumulate_window(self._guard, flag)
                if due:
                    # the guard-interval read is the step's one
                    # device->host transfer — the 'host_sync' slice
                    # of the step timeline (docs/observability.md)
                    with telemetry.span("host_sync"):
                        bad = opt_mod.read_window_bad(self._guard)
                    if bad and self._guard.drops_updates:
                        # the in-jit select already dropped those
                        # updates on device; un-advance the LR
                        # schedule by the exact count (before record,
                        # which may raise under policy=raise)
                        self._optimizer.num_update -= bad
                    self._scaler.update(overflow=bad > 0)
                    self._guard.record(bad == 0,
                                       dropped=max(bad, 1))
            return

        if guarded:
            grads = [p._grad for p in self._params
                     if p._grad is not None]
            if not opt_mod.guarded_step_begin(self._guard,
                                              self._scaler, grads):
                return
        with telemetry.span("optimizer"):
            for i, p in enumerate(self._params):
                if p._grad is None:
                    continue
                if self._kvstore is not None and \
                        self._update_on_kvstore:
                    self._kvstore.push(i, p.grad(), priority=-i)
                    self._kvstore.pull(i, out=p.data(), priority=-i)
                elif self._kvstore is not None:
                    self._kvstore.push(i, p.grad(), priority=-i)
                    self._kvstore.pull(i, out=p.grad(), priority=-i)
                    self._updater(i, p.grad(), p.data())
                else:
                    self._updater(i, p.grad(), p.data())

    def allreduce_grads(self):
        """Explicit grad reduction without update (API parity; on a
        mesh the psum already happened inside the compiled step)."""
        if not self._kv_initialized:
            self._init_kvstore()

    def update(self, batch_size, ignore_stale_grad=False):
        self.step(batch_size, ignore_stale_grad)

    def save_states(self, fname):
        import pickle

        from .. import resilience
        if self._fused_active() and self._fstate is not None:
            import numpy as np
            tree = jax.tree_util.tree_map(np.asarray, self._fstate)
            resilience.atomic_save(
                fname, lambda f: pickle.dump({"fused": tree}, f))
            return
        resilience.atomic_write_bytes(fname,
                                      self._updater.get_states())

    def load_states(self, fname):
        import pickle

        from .. import resilience
        raw = resilience.read_validated_bytes(fname)
        # decode under the corruption guard, apply outside it
        obj = resilience.decode_or_corrupt(
            fname, lambda: pickle.loads(raw))
        if isinstance(obj, dict) and "fused" in obj:
            if self._fused_update is None:
                self._init_fused()
            if not self._fused_active():
                raise ValueError(
                    "states file was saved by the fused update path "
                    "but this Trainer's optimizer has no functional "
                    "counterpart (or runs on a multi-worker kvstore); "
                    "cannot restore")
            self._fstate = jax.tree_util.tree_map(jnp.asarray,
                                                  obj["fused"])
            return
        self._updater.set_states(obj)
