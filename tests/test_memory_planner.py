"""Memory-pressure survival (docs/memory.md): the analytic HBM
planner cross-checked against XLA's ``memory_analysis()`` on the
bench train graphs, the preflight degrade ladder
(remat -> grad_accum -> typed MemoryPlanError), the runtime
``mem:oom`` guard (one rung + a single retry, bitwise-identical loss
on the remat rung), the exit-15 contracts, planner-sized serving KV
pools, and the lint rule that keeps broad handlers from swallowing a
real RESOURCE_EXHAUSTED untyped."""
import importlib
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import parallel, telemetry, tracing
from incubator_mxnet_tpu import resilience as rz
from incubator_mxnet_tpu import symbol as symmod
from incubator_mxnet_tpu.perf import memory_planner as mp

import _graphs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# which placeholder names in each bench graph are inputs (the rest
# are parameters the planner must count as resident)
GRAPH_INPUTS = {
    "mlp": {"data", "label"},
    "resnet_block": {"data"},
    "transformer_step": {"tokens", "labels"},
}


@pytest.fixture(autouse=True)
def _clean_slate(monkeypatch):
    for var in ("MXTPU_FAULT_SPEC", "MXTPU_MEM_POLICY",
                "MXTPU_HBM_BYTES", "MXTPU_MEM_GATE_MARGIN",
                "MXTPU_TRACE_DUMP"):
        monkeypatch.delenv(var, raising=False)
    rz.reset_faults()
    telemetry.get_registry().reset()
    tracing.reset_for_tests()
    yield
    rz.reset_faults()
    telemetry.get_registry().reset()
    tracing.reset_for_tests()


def _counter(name):
    return telemetry.get_registry().counter(name).value


def _gauge(name):
    return telemetry.get_registry().gauge(name).value


# -------------------------------------------------------------- sizing
def test_tree_bytes_counts_metadata_only():
    tree = {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32),
            "b": jax.ShapeDtypeStruct((32,), jnp.bfloat16),
            "i": jax.ShapeDtypeStruct((7,), jnp.int32)}
    assert mp.tree_bytes(tree) == 64 * 32 * 4 + 32 * 2 + 7 * 4
    assert mp.max_leaf_bytes(tree) == 64 * 32 * 4
    # bare shapes default to 4-byte elements
    assert mp.tree_bytes([np.zeros((3, 5), np.float32)]) == 60


def test_next_divisor_walks_the_ladder():
    assert mp.next_divisor(32, 1) == 2
    assert mp.next_divisor(32, 2) == 4
    assert mp.next_divisor(12, 2) == 3
    assert mp.next_divisor(12, 6) == 12
    assert mp.next_divisor(12, 12) is None
    assert mp.next_divisor(0, 1) is None


def test_memory_plan_total_and_describe():
    plan = mp.MemoryPlan(params=10 << 20, grads=5 << 20,
                         activations=2 << 20, meta={"site": "t"})
    assert plan.total() == float(17 << 20)
    text = plan.describe()
    assert "params=10.0MB" in text and "site=t" in text
    d = plan.as_dict()
    assert d["total"] == plan.total() and d["site"] == "t"


# ---------------------------------------------------------- grads model
def _mlp_liveness():
    s, shapes = _graphs._graph_mlp(symmod)
    return mp.symbol_liveness(s, shapes,
                              input_names=GRAPH_INPUTS["mlp"])


def test_plan_grads_follow_donation_and_accum():
    live = _mlp_liveness()
    donate = mp.plan_memory(liveness=live, donate=True)
    keep = mp.plan_memory(liveness=live, donate=False)
    # donation aliases the masters: only the working gradient stays
    assert donate.grads == live["max_param_bytes"]
    assert keep.grads == live["params_bytes"]
    assert keep.outputs > 0.0 and donate.outputs == 0.0
    # accumulation materializes the full accumulator tree
    accum = mp.plan_memory(liveness=live, grad_accum=2)
    assert accum.grads == live["params_bytes"] + live["max_param_bytes"]
    assert accum.activations == pytest.approx(donate.activations / 2)
    # eval has no gradient term and peaks at the forward watermark
    ev = mp.plan_memory(liveness=live, train=False)
    assert ev.grads == 0.0
    assert ev.activations == live["forward_peak_bytes"]


def test_batch_shards_shrink_batch_carried_terms():
    live = _mlp_liveness()
    one = mp.plan_memory(liveness=live)
    four = mp.plan_memory(liveness=live, batch_shards=4)
    assert four.activations == pytest.approx(one.activations / 4)
    assert four.inputs == pytest.approx(one.inputs / 4)
    assert four.params == one.params


# ----------------------------------------------- cross-check vs XLA
def _train_compiled(s, shapes, inputs, grad_accum=1, remat=False,
                    sharding=None):
    """Compile one donated SGD train step straight from the Symbol
    (abstract lowering only — nothing runs), so memory_analysis()
    reports the same step shape the planner models.  ``sharding``
    places the arguments (tests/test_tpu_compile.py: a described
    v5e chip); the default is this process's backend."""
    import functools

    from incubator_mxnet_tpu.executor import build_graph_fn
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)

    arg_names = s.list_arguments()
    aux_names = s.list_auxiliary_states()
    known = {k: v for k, v in shapes.items()
             if k in set(arg_names) | set(aux_names)}
    arg_shapes, _, aux_shapes = s.infer_shape_partial(**known)
    run = build_graph_fn(s)
    all_args = {n: tuple(sh) for n, sh in zip(arg_names, arg_shapes)}
    auxs = {n: sds(tuple(sh), np.float32)
            for n, sh in zip(aux_names, aux_shapes)}
    params = {n: sds(sh, np.float32)
              for n, sh in all_args.items() if n not in inputs}
    datas = {n: sds(
        sh, np.int32 if ("label" in n or "tokens" in n)
        else np.float32) for n, sh in all_args.items() if n in inputs}
    rng = sds((2,), np.uint32)

    def lossf(p, d, av, r):
        fwd = run({**p, **{k: v.astype(np.float32)
                           for k, v in d.items()}}, av, r, True)
        outs = fwd[0] if isinstance(fwd, tuple) else fwd
        loss = outs[-1] if isinstance(outs, (list, tuple)) else outs
        return jnp.mean(loss)

    lf = jax.checkpoint(lossf) if remat else lossf

    def step(p, d, av, r):
        if grad_accum <= 1:
            loss, g = jax.value_and_grad(lf)(p, d, av, r)
        else:
            def micro(carry, dslice):
                gsum, lsum = carry
                mloss, mg = jax.value_and_grad(lf)(p, dslice, av, r)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b, gsum, mg)
                return (gsum, lsum + mloss), None

            dm = {k: d[k].reshape(
                (grad_accum, d[k].shape[0] // grad_accum)
                + d[k].shape[1:]) for k in sorted(datas)}
            zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
            (g, loss), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32)), dm)
        newp = jax.tree_util.tree_map(
            lambda a, b: a - 0.1 * b, p, g)
        return loss, newp

    return (jax.jit(step, donate_argnums=(0,))
            .lower(params, datas, auxs, rng).compile())


# The band for resnet_block-1 is 30%, not 20%: XLA:CPU's accounting
# moved, the planner did not.  The plan is 1,296,384 B today as it was
# under an earlier jaxlib (1.24 MiB, -6.1% of that XLA:CPU's 1.32
# MiB); the XLA:CPU of the installed jaxlib 0.9.0 assigns this conv
# block 1,347,328 B of temporaries and comes to 1.69 MiB (-26.9%),
# the other four cases moving by 0-3%.  Those temporaries belong to
# the CPU backend's convolutions; the v5e compiler gives the same
# step none.  The planner plans for the TPU, so its binding check is
# test_planner_never_under_the_v5e_compiler in test_tpu_compile.py;
# this one stays as a detector of drift in the planner's arithmetic.
@pytest.mark.parametrize("graph,accum,band", [
    ("mlp", 1, 0.20), ("mlp", 2, 0.20),
    ("resnet_block", 1, 0.30), ("resnet_block", 2, 0.20),
    ("transformer_step", 1, 0.20),
], ids=["mlp-1", "mlp-2", "resnet_block-1", "resnet_block-2",
        "transformer_step-1"])
def test_planner_within_20pct_of_xla(graph, accum, band):
    s, shapes = getattr(_graphs, f"_graph_{graph}")(symmod)
    inputs = GRAPH_INPUTS[graph]
    compiled = _train_compiled(s, shapes, inputs, grad_accum=accum)
    xla = mp.xla_live_bytes(compiled.memory_analysis())
    if not xla:
        pytest.skip("backend reports no memory analysis")
    plan = mp.plan_memory(s, shapes, input_names=inputs,
                          grad_accum=accum, donate=True)
    rel = (plan.total() - xla) / xla
    assert abs(rel) <= band, (
        f"{graph} accum={accum}: planner {plan.total():.0f} vs XLA "
        f"{xla:.0f} ({rel:+.1%}) — {plan.describe()}")


@pytest.mark.parametrize("graph",
                         ["mlp", "resnet_block", "transformer_step"])
def test_remat_and_accum_move_the_plan_directionally(graph):
    # planner-only: CPU XLA does not shrink temps under
    # jax.checkpoint, so remat is asserted against the model itself
    s, shapes = getattr(_graphs, f"_graph_{graph}")(symmod)
    live = mp.symbol_liveness(s, shapes,
                              input_names=GRAPH_INPUTS[graph])
    base = mp.plan_memory(liveness=live)
    remat = mp.plan_memory(liveness=live, remat=True)
    assert remat.activations <= base.activations
    assert remat.total() <= base.total()
    accum = mp.plan_memory(liveness=live, grad_accum=2)
    assert accum.activations < base.activations
    assert accum.grads > base.grads


def test_remat_strictly_helps_on_a_deep_graph():
    s, shapes = _graphs._graph_resnet_block(symmod)
    live = mp.symbol_liveness(s, shapes,
                              input_names=GRAPH_INPUTS["resnet_block"])
    assert live["forward_peak_bytes"] < live["retained_bytes"]


# ------------------------------------------------------------- preflight
def test_preflight_takes_remat_rung_and_records_it(monkeypatch):
    live = _mlp_liveness()

    def make(remat, accum):
        return mp.plan_memory(liveness=live, remat=remat,
                              grad_accum=accum)

    base, remat = make(False, 1).total(), make(True, 1).total()
    assert remat < base
    monkeypatch.setenv("MXTPU_MEM_GATE_MARGIN", "0")
    monkeypatch.setenv("MXTPU_HBM_BYTES",
                       str(int((base + remat) / 2)))
    res = mp.preflight(make, site="t", can_remat=True, batch_size=32)
    assert res.rungs == ["remat"]
    assert res.remat is True and res.grad_accum == 1
    assert _counter("memory_plan_degrades_total") == 1
    evs = tracing.events("mem_degrade", site="t")
    assert evs and evs[0]["rung"] == "remat"
    assert evs[0]["predicted_bytes"] == base
    assert _gauge("memory_plan_peak_bytes") == remat


def test_preflight_grad_accum_rungs_walk_divisors(monkeypatch):
    def make(remat, accum):
        return mp.MemoryPlan(params=100.0, activations=1000.0 / accum)

    monkeypatch.setenv("MXTPU_MEM_GATE_MARGIN", "0")
    monkeypatch.setenv("MXTPU_HBM_BYTES", "400")
    res = mp.preflight(make, site="t", can_remat=False, batch_size=8)
    assert res.rungs == ["grad_accum=2", "grad_accum=4"]
    assert res.grad_accum == 4 and res.remat is False
    assert res.plan.total() == 350.0


def test_preflight_dry_ladder_raises_typed(monkeypatch):
    def make(remat, accum):
        return mp.MemoryPlan(params=1e12)

    monkeypatch.setenv("MXTPU_HBM_BYTES", "1000")
    with pytest.raises(rz.MemoryPlanError) as ei:
        mp.preflight(make, site="gate", can_remat=True, batch_size=4)
    err = ei.value
    assert err.EXIT_CODE == rz.OOM_EXIT_CODE == 15
    assert err.rungs == ["remat", "grad_accum=2", "grad_accum=4"]
    assert "gate" in str(err) and "params=" in str(err)


def test_preflight_policy_off_and_warn(monkeypatch):
    def make(remat, accum):
        return mp.MemoryPlan(params=1e12)

    monkeypatch.setenv("MXTPU_HBM_BYTES", "1000")
    monkeypatch.setenv("MXTPU_MEM_POLICY", "off")
    assert mp.preflight(make, site="t") is None
    monkeypatch.setenv("MXTPU_MEM_POLICY", "warn")
    res = mp.preflight(make, site="t", can_remat=True, batch_size=4)
    assert res.rungs == [] and res.plan.total() == 1e12
    assert _counter("memory_plan_degrades_total") == 0


# --------------------------------------------------- train-step wiring
def _tiny_step(**kw):
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(mx.gluon.nn.Dense(16, activation="relu"))
        net.add(mx.gluon.nn.Dense(4))
    net.initialize(mx.initializer.Xavier())
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(8, 12), jnp.float32)
    y = jnp.asarray(rs.randint(0, 4, (8,)), jnp.int32)
    step = parallel.ShardedTrainStep(
        net, optimizer="sgd",
        optimizer_params=dict(learning_rate=0.1),
        mesh=parallel.make_mesh(), example_args=[x], **kw)
    return step, x, y


def test_sharded_step_preflight_populates_plan():
    step, x, y = _tiny_step()
    step(x, y, rng=jax.random.PRNGKey(0))
    assert step._mem_plan is not None
    assert step._mem_plan.total() > 0
    assert _gauge("memory_plan_peak_bytes") == step._mem_plan.total()


def test_no_planning_on_the_hot_path(monkeypatch):
    step, x, y = _tiny_step()
    step(x, y, rng=jax.random.PRNGKey(0))

    def boom(*a, **k):   # pragma: no cover - fails the test if hit
        raise AssertionError("memory planning ran on the step path")

    monkeypatch.setattr(mp, "preflight", boom)
    monkeypatch.setattr(mp, "plan_memory", boom)
    step(x, y, rng=jax.random.PRNGKey(1))


def test_module_bind_gated_by_preflight(monkeypatch):
    from incubator_mxnet_tpu import sym
    monkeypatch.setenv("MXTPU_HBM_BYTES", "1000")
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = sym.SoftmaxOutput(net, sym.Variable("softmax_label"),
                            name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    with pytest.raises(rz.MemoryPlanError) as ei:
        mod.bind(data_shapes=[("data", (8, 20))],
                 label_shapes=[("softmax_label", (8,))])
    assert "module_bind" in str(ei.value)
    # warn policy lets the same bind through
    monkeypatch.setenv("MXTPU_MEM_POLICY", "warn")
    mod2 = mx.mod.Module(net, context=mx.cpu())
    mod2.bind(data_shapes=[("data", (8, 20))],
              label_shapes=[("softmax_label", (8,))])
    assert mod2.binded


# ------------------------------------------------------ runtime mem:oom
def test_injected_oom_takes_one_rung_and_retries(monkeypatch):
    ref_step, x, y = _tiny_step()
    ref = [float(np.asarray(
        ref_step(x, y, rng=jax.random.PRNGKey(s))))
        for s in range(4)]

    monkeypatch.setenv("MXTPU_FAULT_SPEC", "mem:oom:2:error")
    rz.reset_faults()
    step, x, y = _tiny_step()
    assert step.remat is False
    got = [float(np.asarray(step(x, y, rng=jax.random.PRNGKey(s))))
           for s in range(4)]
    # the second crossing blew up; the guard took the remat rung and
    # retried the same batch once — remat changes the schedule, not
    # the math, so every loss is bitwise identical to the clean twin
    assert step.remat is True
    assert got == ref
    assert _counter("oom_retries_total") == 1
    evs = tracing.events("mem_degrade", cause="runtime_oom")
    assert evs and evs[0]["rung"] == "remat"
    assert evs[0]["site"] == "sharded_train_step"


def test_mem_fault_grammar_is_error_only():
    specs = rz.parse_fault_spec("mem:oom:2:error")
    assert specs == [("mem", "oom", 2, "error")]
    with pytest.raises(ValueError):
        rz.parse_fault_spec("mem:oom:1:hang")


def test_is_oom_classifier():
    assert rz.is_oom(RuntimeError("RESOURCE_EXHAUSTED: hbm"))
    assert rz.is_oom(RuntimeError("Allocator ran out of memory"))
    assert not rz.is_oom(RuntimeError("XLA compilation cached"))
    assert not rz.is_oom(rz.MemoryPlanError("t"))
    assert rz.as_oom_error(ValueError("shape mismatch"), "t") is None
    oom = rz.as_oom_error(RuntimeError("Out of memory"), "site_x",
                          plan=mp.MemoryPlan(params=4.0))
    assert isinstance(oom, rz.OomError)
    assert "site_x" in str(oom)


# ------------------------------------------------------- exit contracts
def _run_py(code, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=240)


def test_exithook_maps_memory_errors_to_exit_15():
    for err in ("OomError('t')",
                "MemoryPlanError('t', rungs=['remat'])"):
        res = _run_py(
            "import incubator_mxnet_tpu.resilience as rz\n"
            "rz.install_diverged_exithook()\n"
            f"raise rz.{err}\n")
        assert res.returncode == 15, res.stderr
        assert err.split("(")[0] in res.stderr


def test_policy_off_dies_loudly_with_dump(tmp_path):
    dump = tmp_path / "flight.jsonl"
    code = (
        "import numpy as np, jax, jax.numpy as jnp\n"
        "import incubator_mxnet_tpu as mx\n"
        "from incubator_mxnet_tpu import parallel\n"
        "import incubator_mxnet_tpu.resilience as rz\n"
        "rz.install_diverged_exithook()\n"
        "mx.random.seed(0)\n"
        "net = mx.gluon.nn.HybridSequential()\n"
        "with net.name_scope():\n"
        "    net.add(mx.gluon.nn.Dense(8, in_units=12))\n"
        "net.initialize(mx.initializer.Xavier())\n"
        "x = jnp.ones((8, 12), jnp.float32)\n"
        "y = jnp.zeros((8,), jnp.int32)\n"
        "step = parallel.ShardedTrainStep(net, optimizer='sgd',\n"
        "    optimizer_params=dict(learning_rate=0.1),\n"
        "    mesh=parallel.make_mesh())\n"
        "step(x, y, rng=jax.random.PRNGKey(0))\n")
    res = _run_py(code, extra_env={
        "MXTPU_MEM_POLICY": "off",
        "MXTPU_FAULT_SPEC": "mem:oom:1:error",
        "MXTPU_TRACE_DUMP": str(dump),
    })
    assert res.returncode == 15, res.stderr
    assert "OomError" in res.stderr
    dumps = list(tmp_path.glob("flight*.jsonl"))
    assert dumps and dumps[0].stat().st_size > 0


# ----------------------------------------------------- serving KV pools
def test_serving_auto_num_blocks_sizes_from_headroom(monkeypatch):
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import (
        TransformerLM)
    from incubator_mxnet_tpu.serving import ServingEngine

    def tiny():
        mx.random.seed(0)
        net = TransformerLM(37, d_model=32, n_layers=2, n_heads=4,
                            max_len=64)
        net.initialize(mx.initializer.Xavier())
        return net

    monkeypatch.setenv("MXTPU_HBM_BYTES", str(64 << 20))
    eng = ServingEngine(tiny(), max_batch=2, block_size=4,
                        num_blocks="auto")
    assert eng.auto_blocks
    # plenty of headroom: capped at a full context row per slot + scratch
    assert eng.num_blocks == eng.max_batch * eng.max_blocks + 1
    assert _gauge("memory_plan_peak_bytes") > 0
    # a chip the weights alone overflow refuses with a typed error
    monkeypatch.setenv("MXTPU_HBM_BYTES", "20000")
    with pytest.raises(rz.MemoryPlanError) as ei:
        ServingEngine(tiny(), max_batch=2, block_size=4,
                      num_blocks="auto")
    assert "serving_engine" in str(ei.value)


# -------------------------------------------------------------- lint
def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "lint", os.path.join(REPO, "ci", "lint.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    return lint


def test_lint_flags_unguarded_broad_except(tmp_path):
    lint = _load_lint()
    d = tmp_path / "incubator_mxnet_tpu" / "parallel"
    d.mkdir(parents=True)
    f = d / "step.py"
    f.write_text(
        "class S:\n"
        "    def run(self, x):\n"
        "        try:\n"
        "            return self._step(x)\n"
        "        except Exception:\n"
        "            return None\n")
    assert any("typed OOM guard" in p for p in lint.check_file(f))

    f.write_text(   # routed through the typed guard: clean
        "class S:\n"
        "    def run(self, x):\n"
        "        try:\n"
        "            return self._step(x)\n"
        "        except Exception as exc:\n"
        "            oom = as_oom_error(exc, 'run')\n"
        "            if oom is not None:\n"
        "                raise oom from exc\n"
        "            raise\n")
    assert not any("typed OOM guard" in p for p in lint.check_file(f))

    f.write_text(   # annotated escape hatch: clean
        "class S:\n"
        "    def run(self, x):\n"
        "        try:\n"
        "            return self._step(x)\n"
        "        except Exception:   # oom-ok: probing optional API\n"
        "            return None\n")
    assert not any("typed OOM guard" in p for p in lint.check_file(f))

    f.write_text(   # broad except with no execute call inside: clean
        "class S:\n"
        "    def run(self, x):\n"
        "        try:\n"
        "            return int(x)\n"
        "        except Exception:\n"
        "            return 0\n")
    assert not any("typed OOM guard" in p for p in lint.check_file(f))
