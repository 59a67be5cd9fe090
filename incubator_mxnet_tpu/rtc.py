"""Runtime custom-kernel registration — the TPU answer to ``mx.rtc``.

The reference lets users hand the runtime raw CUDA source and call it
as a kernel (ref: python/mxnet/rtc.py:1 CudaModule/get_kernel,
include/mxnet/rtc.h:136).  On TPU the user-extensible kernel layer is
**Pallas**: you write a Python kernel over VMEM refs, Mosaic compiles
it for the systolic array, and here it becomes a first-class operator
— visible from ``nd`` (eager), ``sym`` (graphs), and any Gluon
``HybridBlock``, differentiable if you give it a VJP, and fused into
jit-compiled executables like every built-in op.

Two layers:

``compile_kernel``
    pallas_call wrapper: the kernel is Mosaic-compiled where the call
    is lowered for a TPU and runs through the Pallas interpreter
    anywhere else, so custom kernels are testable on CPU and in CI.

``register``
    put any jit-compatible function — a compiled Pallas kernel or
    plain jax.numpy — into the central op registry and onto the
    nd/sym namespaces.

Example (see examples/custom_pallas_kernel.py and tests/test_rtc.py)::

    from jax.experimental import pallas as pl

    def scale_kernel(x_ref, o_ref, *, alpha):
        o_ref[...] = x_ref[...] * alpha

    fn = rtc.compile_kernel(
        scale_kernel,
        out_shape=lambda x, alpha=2.0: jax.ShapeDtypeStruct(
            x.shape, x.dtype))
    rtc.register("my_scale", fn,
                 vjp=(lambda x, alpha=2.0: (fn(x, alpha=alpha), None),
                      lambda alpha, res, g: (g * alpha,)))

    y = mx.nd.my_scale(mx.nd.ones((4, 4)), alpha=3.0)   # eager
    s = mx.sym.my_scale(mx.sym.Variable("x"), alpha=3.0)  # symbolic
"""
import functools

import jax

from .ops.registry import OPS, OpDef

__all__ = ["compile_kernel", "register", "pallas_call"]


def pallas_call(kernel, *, interpret=None, **pallas_kwargs):
    """``pl.pallas_call`` that compiles the kernel for the TPU and
    interprets it everywhere else.

    With ``interpret=None`` the choice is made when the call is
    lowered, from the platform it is lowered for — where the operands
    live, or what the enclosing ``jit`` targets — never from the
    process's default backend: on a host with a TPU, an eager forward
    over CPU-placed operands must still get the interpreter.  The
    ``jit`` gives an eager call that lowering context."""
    from jax.experimental import pallas as pl

    if interpret is not None:
        return pl.pallas_call(kernel, interpret=bool(interpret),
                              **pallas_kwargs)
    compiled = pl.pallas_call(kernel, interpret=False, **pallas_kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True,
                                 **pallas_kwargs)

    @jax.jit
    def call(*arrays):
        return jax.lax.platform_dependent(
            *arrays, tpu=compiled, default=interpreted)

    return call


def compile_kernel(kernel, out_shape, *, interpret=None,
                   grid=None, in_specs=None, out_specs=None,
                   **pallas_kwargs):
    """Wrap a Pallas kernel into a jit-compatible callable.

    Parameters
    ----------
    kernel : Pallas kernel ``fn(*in_refs, *out_refs, **params)``.
        Static params are forwarded from the call site by keyword.
    out_shape : ``jax.ShapeDtypeStruct`` (or list of them), or a
        callable ``(*arrays, **params) -> out_shape`` evaluated per
        call — shape polymorphism the CUDA-RTC analog never had.
    interpret : force Pallas interpret mode on or off.  Default
        ``None``: compiled where the call is placed on a TPU,
        interpreted elsewhere (see :func:`pallas_call`).
    grid, in_specs, out_specs, **pallas_kwargs :
        forwarded to ``pallas_call`` (same semantics; may each be a
        callable of ``(*arrays, **params)`` for shape-dependent
        tiling).
    """
    def call(*arrays, **params):
        ipret = params.pop("_interpret", interpret)

        def resolve(v):
            return v(*arrays, **params) if callable(v) else v

        kw = dict(pallas_kwargs)
        for k, v in (("grid", grid), ("in_specs", in_specs),
                     ("out_specs", out_specs)):
            if v is not None:
                kw[k] = resolve(v)
        bound = functools.partial(kernel, **params) if params \
            else kernel
        return pallas_call(
            bound, out_shape=resolve(out_shape), interpret=ipret,
            **kw)(*arrays)

    call.__name__ = getattr(kernel, "__name__", "pallas_kernel")
    call.__doc__ = kernel.__doc__
    return call


def register(name, fn, *, vjp=None, arg_names=None,
             differentiable=None, num_outputs=1, aliases=(),
             **opdef_kwargs):
    """Register ``fn`` as operator ``name`` on nd/sym/Gluon surfaces.

    Parameters
    ----------
    fn : jit-compatible ``(*jnp_arrays, **static_params) -> array(s)``
        — typically the result of :func:`compile_kernel`.
    vjp : optional ``(fwd, bwd)`` pair giving the op a custom
        gradient (``jax.custom_vjp`` convention):
        ``fwd(*arrays, **params) -> (out, residuals)`` and
        ``bwd(*param_values, residuals, cotangent) -> grads`` where
        param_values are the op's static params in sorted-name order.
        Without a vjp the op differentiates through ``fn`` itself if
        possible (fine for plain-jax fns; Pallas kernels usually
        need one).
    arg_names : tensor input names for the symbolic frontend
        (defaults to fn's positional signature).
    aliases : extra registry names.

    Returns the eager (``nd``) function.
    """
    if name in OPS:
        raise ValueError(
            f"op '{name}' already exists; rtc.register cannot "
            "shadow a built-in or an earlier custom kernel")
    clashes = [a for a in aliases if a in OPS]
    if clashes:            # validate BEFORE mutating the registry
        raise ValueError(f"aliases {clashes} conflict with existing ops")
    if vjp is not None:
        vjp_fwd, vjp_bwd = vjp
        base = fn
        # static-param defaults come from the fwd rule's signature, so
        # the bwd rule sees the SAME param values whether the caller
        # passed them or relied on defaults
        import inspect
        try:
            fwd_defaults = {
                p.name: p.default
                for p in inspect.signature(vjp_fwd).parameters.values()
                if p.default is not p.empty}
        except (TypeError, ValueError):
            fwd_defaults = {}
        vjp_cache = {}   # params-tuple -> custom_vjp fn (trace cache)

        def _build(full):
            keys = sorted(full)

            @jax.custom_vjp
            def inner(*t):
                return base(*t, **full)

            inner.defvjp(
                lambda *t: vjp_fwd(*t, **full),
                lambda res, g: tuple(
                    vjp_bwd(*(full[k] for k in keys), res, g)))
            return inner

        @functools.wraps(fn)
        def fn(*arrays, **params):  # noqa: F811 — deliberate rewrap
            full = {**fwd_defaults, **params}
            try:    # unhashable static params (lists...) skip caching
                key = tuple(sorted(full.items()))
                inner = vjp_cache.get(key)
            except TypeError:
                return _build(full)(*arrays)
            if inner is None:
                inner = vjp_cache[key] = _build(full)
            return inner(*arrays)

        if differentiable is None:
            differentiable = True
    if differentiable is None:
        differentiable = True
    # infer arg_names from the *original* callable's signature when
    # not given (compile_kernel's wrapper is (*arrays, **params))
    if arg_names is None:
        import inspect
        try:
            sig = inspect.signature(fn)
            arg_names = [p.name for p in sig.parameters.values()
                         if p.kind in (p.POSITIONAL_ONLY,
                                       p.POSITIONAL_OR_KEYWORD)
                         and p.default is p.empty
                         and not p.name.startswith("_")]
        except (TypeError, ValueError):
            arg_names = []
        if not arg_names:
            # compile_kernel wrappers expose only *arrays, so a
            # multi-input kernel registered without explicit
            # arg_names would silently become 1-ary symbolically
            # (advisor r4) — tell the user how to fix it
            import warnings
            warnings.warn(
                f"rtc.register({name!r}): cannot infer arg_names "
                "from the function signature (it takes *arrays); "
                "defaulting to ['data'] (single input).  Pass "
                "arg_names=[...] explicitly for multi-input kernels "
                "used symbolically.", stacklevel=2)
            arg_names = ["data"]
    op = OpDef(name, fn, num_outputs=num_outputs,
               arg_names=arg_names, differentiable=differentiable,
               **opdef_kwargs)
    OPS[name] = op
    ndf = _attach_frontends(name, op)
    for a in aliases:
        OPS[a] = op
        _attach_frontends(a, op)
    _RTC_ALIASES[name] = tuple(aliases)
    return ndf


def _attach_frontends(name, op):
    """Late-bind the new op onto the already-populated nd and sym
    namespaces (import-time codegen handles built-ins; custom kernels
    arrive after import)."""
    from . import ndarray as nd_mod
    from . import symbol as sym_mod
    from .ndarray.register import make_nd_func
    from .symbol.register import make_sym_func

    ndf = make_nd_func(name, op)
    symf = make_sym_func(name, op)
    for mod, f in ((nd_mod, ndf), (sym_mod, symf)):
        target = mod._internal if name.startswith("_") and \
            hasattr(mod, "_internal") else mod
        setattr(target, name, f)
    # the package-level `mx.nd` / `mx.sym` may alias these modules;
    # nothing else caches per-op lookups, so this is sufficient
    return ndf


_RTC_ALIASES = {}    # primary name -> aliases, for unregister


def unregister(name):
    """Remove a custom op registered by :func:`register` — including
    its aliases (testing / re-registration)."""
    from . import ndarray as nd_mod
    from . import symbol as sym_mod
    for n in (name,) + _RTC_ALIASES.pop(name, ()):
        OPS.pop(n, None)
        for mod in (nd_mod, sym_mod):
            target = mod._internal if n.startswith("_") and \
                hasattr(mod, "_internal") else mod
            if hasattr(target, n):
                delattr(target, n)
