"""SymbolTrainStep: one compiled fwd+bwd+optimizer step for a Symbol
graph over a device mesh — the `kvstore='tpu'` execution path of the
Module frontend.

This replaces the reference's DataParallelExecutorGroup, which slices
each batch across devices and allreduces gradients through KVStore
(ref: python/mxnet/module/executor_group.py:99,
python/mxnet/model.py _update_params_on_kvstore:105).  Here the whole
training iteration — graph forward, implicit-loss backward (the
Output-op ones-cotangent contract), gradient mean over the 'dp' mesh
axis (XLA inserts the psum), and the functional optimizer update — is
a single jit executable whose batch inputs are laid out sharded over
'dp'.

Learning rate is a *traced scalar argument* so lr schedulers step
without recompiling; lr_mult/wd_mult become per-leaf multiplier trees
(ref: python/mxnet/optimizer.py _get_lr/_get_wd).
"""
import time

import numpy as np

import jax
import jax.numpy as jnp

from .. import tracing
from ..executor import build_graph_fn, _ones_ct
from .data_parallel import _owned_put_tree, _copy_tree
from .mesh import make_mesh, replicated, shard_batch
from . import optim as foptim

__all__ = ["SymbolTrainStep"]


class SymbolTrainStep:
    """Compiled mesh training step over a bound Symbol.

    Parameters
    ----------
    symbol : Symbol — the full graph incl. loss-output heads
    param_vals / aux_vals : dict[str, jax.Array] initial values
    input_names : ordered data+label variable names fed per batch
    optimizer : FunctionalOptimizer (or name) applied in-jit
    rescale_grad : float — reference Module semantics (1/global-batch)
    lr_mults / wd_mults : per-param multipliers (name -> float)
    """

    def __init__(self, symbol, param_vals, aux_vals, input_names,
                 optimizer="sgd", optimizer_params=None, mesh=None,
                 rescale_grad=1.0, lr_mults=None, wd_mults=None,
                 batch_axis=0, numeric_guard=False,
                 guard_select=None):
        self.mesh = mesh if mesh is not None else make_mesh()
        # numeric_guard=True compiles the step-sentinel variant: the
        # gradients reduce to one in-jit finiteness scalar
        # (optimizer.all_finite), exposed as ``last_finite`` for the
        # host's guard-interval read.  With ``guard_select`` (default
        # = guarded; pass False for policy=warn, whose contract is to
        # apply bad updates) the whole update — params, aux,
        # optimizer state — additionally goes through a
        # where(finite, new, old) select, so EVERY step is protected
        # on device.  A traced ``poison`` multiplier carries the
        # grad:nonfinite fault injection without recompiles
        # (docs/numeric_stability.md).
        self._guarded = bool(numeric_guard)
        self._guard_select = self._guarded if guard_select is None \
            else bool(guard_select)
        self.last_finite = None
        # the mesh step compiles the same optimized graph the
        # single-device Executor does (MXTPU_GRAPH_OPT; rng fold
        # indices are pinned, so the dropout stream is unchanged)
        from ..graph.passes import optimize_symbol
        run_symbol, self.graph_report = optimize_symbol(symbol)
        self._symbol = run_symbol
        self._run = build_graph_fn(run_symbol)
        self._param_names = tuple(sorted(param_vals))
        self._input_names = tuple(input_names)
        self._batch_axis = batch_axis
        if isinstance(optimizer, str):
            self.opt = foptim.create(optimizer,
                                     **(optimizer_params or {}))
        else:
            self.opt = optimizer
        self.rescale_grad = float(rescale_grad)
        self._lr_mults = {n: float((lr_mults or {}).get(n, 1.0))
                          for n in self._param_names}
        self._wd_mults = {n: float((wd_mults or {}).get(n, 1.0))
                          for n in self._param_names}

        rep = {n: replicated(self.mesh) for n in param_vals}
        self.params = _owned_put_tree(dict(param_vals), rep)
        arep = {n: replicated(self.mesh) for n in aux_vals}
        self.aux = _owned_put_tree(dict(aux_vals), arep)
        self.opt_state = self.opt.init(self.params)
        self._step = None
        self._eval = None
        # preflight HBM gate (docs/memory.md): plan accepted at the
        # first call, before the compile; None when planning failed
        self._mem_plan = None
        # device-memory attribution (docs/observability.md): the
        # step owns the job's params and optimizer state on device;
        # weakref providers so a dropped step stops being counted
        def _param_arrays(st):
            return list(st.params.values()) + list(st.aux.values())

        def _opt_arrays(st):
            return jax.tree_util.tree_leaves(st.opt_state)

        self._mem_unregister = tracing.register_param_opt_providers(
            self, _param_arrays, _opt_arrays)

    # ------------------------------------------------------------ build
    def _in_shard(self, ndim):
        return shard_batch(self.mesh, ndim, self._batch_axis)

    def _build(self, inputs):
        run, opt = self._run, self.opt
        pnames = self._param_names
        scale = self.rescale_grad
        lr_mults, wd_mults = self._lr_mults, self._wd_mults
        guarded = self._guarded
        guard_select = self._guard_select

        def step(params, aux, opt_state, inputs, rng, lr, poison):
            def inner(pvals):
                merged = dict(inputs)
                merged.update(zip(pnames, pvals))
                outs, aux_upd = run(merged, aux, rng, True)
                return outs, aux_upd

            primals = tuple(params[n] for n in pnames)
            (outs, aux_upd), vjp = jax.vjp(inner, primals)
            cts = [_ones_ct(o) for o in outs]
            aux_ct = {k: (np.zeros(v.shape, jax.dtypes.float0)
                          if not jnp.issubdtype(v.dtype, jnp.floating)
                          else jnp.zeros(v.shape, v.dtype))
                      for k, v in aux_upd.items()}
            (gvals,) = vjp((cts, aux_ct))
            grads = dict(zip(pnames, gvals))
            if guarded:
                grads = {n: g * poison.astype(g.dtype)
                         for n, g in grads.items()}
            new_params, new_opt = opt.update(
                params, grads, opt_state, scale=scale, lr=lr,
                lr_mults=lr_mults, wd_mults=wd_mults)
            new_aux = dict(aux)
            new_aux.update(aux_upd)
            if not guarded:
                return new_params, new_aux, new_opt, outs, True
            from ..optimizer import all_finite
            finite = jnp.asarray(all_finite(list(grads.values())))
            if not guard_select:
                return new_params, new_aux, new_opt, outs, finite
            sel = lambda new, old: jax.tree_util.tree_map(
                lambda a, b: jnp.where(finite, a, b), new, old)
            # a bad step must leave params, batchnorm-style aux
            # updates, AND optimizer state untouched — on device,
            # every step, regardless of host read cadence
            return (sel(new_params, params), sel(new_aux, dict(aux)),
                    sel(new_opt, opt_state), outs, finite)

        rep = replicated(self.mesh)
        p_sh = {n: rep for n in self.params}
        a_sh = {n: rep for n in self.aux}
        in_sh = {n: self._in_shard(v.ndim) for n, v in inputs.items()}
        return jax.jit(
            step,
            in_shardings=(p_sh, a_sh, None, in_sh, None, None, None),
            out_shardings=(p_sh, a_sh, None, None, None),
            donate_argnums=(0, 1, 2))

    def _preflight(self, vals):
        """Consult the analytic HBM plan (docs/memory.md) before the
        first compile.  This step fixes remat/grad_accum at graph
        construction, so the ladder has no rungs here: the plan
        either fits (within MXTPU_MEM_GATE_MARGIN), warns, or raises
        a typed MemoryPlanError per MXTPU_MEM_POLICY.  Planner
        failures on exotic graphs are non-fatal."""
        from ..perf import memory_planner as mp
        from ..resilience import MemoryPlanError
        try:
            shapes = {n: tuple(v.shape) for n, v in vals.items()}
            shapes.update({n: tuple(v.shape)
                           for n, v in self.params.items()})
            shapes.update({n: tuple(v.shape)
                           for n, v in dict(self.aux).items()})
            dtypes = {n: str(v.dtype) for n, v in vals.items()}
            live = mp.symbol_liveness(
                self._symbol, shapes, dtypes=dtypes,
                input_names=[n for n in self._input_names
                             if n in shapes])
            res = mp.preflight(
                lambda r, a: mp.plan_memory(
                    liveness=live,
                    params_bytes=mp.tree_bytes(self.params)
                    + mp.tree_bytes(dict(self.aux)),
                    max_param_bytes=mp.max_leaf_bytes(self.params),
                    optimizer_bytes=mp.tree_bytes(self.opt_state),
                    grad_accum=a, remat=r, donate=True,
                    batch_shards=int(self.mesh.shape.get("dp", 1))),
                site="symbol_train_step",
                device=self.mesh.devices.flat[0])
        except MemoryPlanError:
            raise
        except Exception:
            import logging
            logging.getLogger("mxtpu.memory").debug(
                "memory preflight skipped (planning failed)",
                exc_info=True)
            return
        if res is not None:
            self._mem_plan = res.plan

    # ------------------------------------------------------------ run
    def __call__(self, inputs, rng=None, lr=0.01):
        """Run one step on a global batch.

        inputs: dict name -> array (host or device); returns the list
        of output arrays (replicated loss heads / sharded outputs).
        """
        from ..dist import elastic_probe
        elastic_probe()     # elastic:rank<N> injection (docs/elastic.md)
        if rng is None:
            from .. import random_state
            rng = random_state.next_key()
        from ..resilience import as_oom_error, check_oom
        vals = {n: jnp.asarray(v) if not isinstance(v, jax.Array)
                else v for n, v in inputs.items()}
        compiled = self._step is None
        t0 = time.monotonic()
        try:
            if compiled:
                self._preflight(vals)
                self._step = self._build(vals)
            # mem:oom injection point; free without MXTPU_FAULT_SPEC
            check_oom("symbol_train_step")
            vals = {n: jax.device_put(v, self._in_shard(v.ndim))
                    for n, v in vals.items()}
            poison = 1.0
            if self._guarded:
                from ..optimizer import grad_poison
                poison = grad_poison() or 1.0
            (self.params, self.aux, self.opt_state, outs,
             self.last_finite) = self._step(
                self.params, self.aux, self.opt_state, vals, rng,
                jnp.asarray(lr, jnp.float32),
                jnp.asarray(poison, jnp.float32))
        except Exception as exc:
            # route real RESOURCE_EXHAUSTED (and the injected kind)
            # through the typed guard; this step has no runtime
            # degrade rungs, so the OomError stays loud
            oom = as_oom_error(exc, "symbol_train_step",
                               plan=self._mem_plan)
            if oom is None:
                raise
            raise oom from exc
        if compiled:
            # first call = trace + compile of the whole mesh step;
            # recorded with the batch signature so a rebuilt step
            # (fresh Module bind / rollback) attributes what differed
            tracing.compile_ledger("symbol_train_step").record(
                {"shape": tuple(sorted(
                    (n, tuple(v.shape)) for n, v in vals.items())),
                 "dtype": tuple(sorted(
                     (n, str(v.dtype)) for n, v in vals.items())),
                 "train_flag": True},
                time.monotonic() - t0)
        return outs

    def evaluate(self, inputs, rng=None):
        """Compiled inference forward over the mesh (score/predict)."""
        if rng is None:
            from .. import random_state
            rng = random_state.next_key()
        run = self._run
        if self._eval is None:
            def ev(params, aux, inputs, rng):
                merged = dict(inputs)
                merged.update(params)
                outs, _ = run(merged, aux, rng, False)
                return outs
            self._eval = jax.jit(ev)
        vals = {n: jax.device_put(jnp.asarray(v),
                                  self._in_shard(jnp.asarray(v).ndim))
                for n, v in inputs.items()}
        return self._eval(self.params, self.aux, vals, rng)

    # ------------------------------------------------------------ values
    @property
    def input_names(self):
        """Per-batch graph inputs (data + label variable names)."""
        return self._input_names

    def owned_values(self):
        """(params, aux) copies safe to hand to external holders —
        the step's own buffers are donated next call."""
        return _copy_tree(self.params), _copy_tree(dict(self.aux))

    def set_values(self, param_vals, aux_vals):
        """Replace the step's device values (e.g. after an external
        eager update touched the frontend's copies)."""
        rep = {n: replicated(self.mesh) for n in param_vals}
        self.params = _owned_put_tree(dict(param_vals), rep)
        arep = {n: replicated(self.mesh) for n in aux_vals}
        self.aux = _owned_put_tree(dict(aux_vals), arep)

    # ---------------------------------------------------------- checkpoint
    def save_checkpoint(self, path, step=None, data_state=None):
        """Write params + aux + optimizer state as one sharded
        generation under ``path`` (parallel/checkpoint.py manifest
        format, docs/elastic.md) — the Module frontend's elastic
        checkpoint: each rank writes only its owned slices, and the
        input iterator's ``data_state`` rides in the same generation.
        Returns the generation directory."""
        from . import checkpoint as _ckpt
        tree = {"params": _copy_tree(self.params),
                "aux": _copy_tree(dict(self.aux)),
                "opt_state": _copy_tree(self.opt_state)}
        return _ckpt.save_sharded(
            path, tree, self.mesh, step=step, data_state=data_state,
            extra={"optimizer": foptim.state_structure(
                self.opt_state)})

    def load_checkpoint(self, path):
        """Restore the newest valid generation INTO this step's mesh
        layout — reassembled per-shard from the overlapping source
        slices, so the saving job's mesh shape / world size need not
        match this one's.  Returns the generation's data-iterator
        companion state (or None)."""
        from . import checkpoint as _ckpt
        tree = {"params": self.params, "aux": dict(self.aux),
                "opt_state": self.opt_state}
        restored, manifest, gen_dir = _ckpt.load_latest(
            path, tree, self.mesh)
        self.params = restored["params"]
        self.aux = restored["aux"]
        self.opt_state = restored["opt_state"]
        return _ckpt.load_data_companion(gen_dir, manifest)
