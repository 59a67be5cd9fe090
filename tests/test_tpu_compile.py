"""Ask the TPU v5e's compiler, without a chip (on-chip-measurement guide
section 2): the Pallas kernels of the main path at the widths
``chip_smoke.py`` runs them, Mosaic-compiled for a *described*
``v5e:2x2`` — what interpret mode cannot show (tiling rules, VMEM
limits, partitioning).  A compile that passes is not a chip run.

Every such test lives in this one file: the topology is described in
a module-scoped fixture, so only the xdist worker that is handed this
file loads the TPU library, and every worker collects the same tests.
"""
import functools
import importlib.util
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import rtc
from incubator_mxnet_tpu import symbol as symmod
from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
    _flash_on_mesh
from incubator_mxnet_tpu.ops.flash import flash_attention
from incubator_mxnet_tpu.perf import memory_planner as mp
from incubator_mxnet_tpu.serving import engine as serving

import _graphs
from test_memory_planner import GRAPH_INPUTS, _train_compiled

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — whatever says "no"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _attention_loss(window):
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window)
        return jnp.sum(out.astype(jnp.float32))
    return loss


# (BH, L, d), window, dtype: the smoke's kernel comparison and LM step
# (128, 1024, 64), a long context, the banded grid, wide heads, f32,
# and the train cell's call with its float32 twin (ops.flash._tiles:
# 1024 x 1024 for bfloat16, 512 x 512 for float32 and for the band)
FLASH_CASES = [
    pytest.param((128, 1024, 64), 0, "bfloat16", id="1024x64"),
    pytest.param((128, 4096, 64), 0, "bfloat16", id="4096x64"),
    pytest.param((128, 1024, 64), 256, "bfloat16", id="1024x64-w256"),
    pytest.param((32, 1024, 128), 0, "bfloat16", id="1024x128"),
    pytest.param((128, 1024, 64), 0, "float32", id="1024x64-f32"),
    pytest.param((64, 2048, 64), 0, "bfloat16", id="2048x64-cell"),
    pytest.param((64, 2048, 64), 0, "float32", id="2048x64-cell-f32"),
]


@pytest.mark.parametrize("shape,window,dtype", FLASH_CASES)
def test_flash_forward_compiles_for_v5e(one_chip, shape, window,
                                        dtype):
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    compiled = jax.jit(_attention_loss(window)).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape,window,dtype", FLASH_CASES)
def test_flash_backward_compiles_for_v5e(one_chip, shape, window,
                                         dtype):
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    compiled = jax.jit(jax.grad(_attention_loss(window),
                                argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    # forward (for the residuals), dq and dk/dv kernels
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_flash_under_dp_mesh_compiles_for_v5e(topo):
    """GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"): under a multi-device mesh the transformer hands each
    device its rows through ``_flash_on_mesh``.  The dp=4 step of
    ``chip_smoke.py --chips 4`` stands on this."""
    mesh = Mesh(np.array(topo.devices).reshape(4, 1, 1, 1, 1),
                mx.parallel.AXES)
    heads, shape = 16, (8 * 16, 1024, 64)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))

    def loss(q, k, v):
        return jnp.sum(_flash_on_mesh(q, k, v, mesh, heads, 0)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(_attention_loss(0)).lower(x, x, x).compile()


@pytest.mark.parametrize("rows,width", [
    pytest.param(64, 768, id="decode-64x8"),
    pytest.param(1024, 768, id="prefill-1024x8"),
    pytest.param(40, 768, id="rows-no-whole-tile"),
    pytest.param(64, 96, id="width-no-whole-lane")])
def test_routed_layer_compiles_for_v5e(one_chip, rows, width):
    """``ops.moe.routed_ffn_fn`` lowered for the chip: rows that make
    whole tiles of 128 pairs at widths of whole lanes go through the
    grouped Pallas product (gate, up, down: three kernels), anything
    else through the loop over tiles, which is plain XLA."""
    from incubator_mxnet_tpu.ops.moe import routed_ffn_fn
    n, d, k = 16, 2048, 8

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=one_chip)

    def layer(x, router, gate, up, down):
        return routed_ffn_fn(x, router, gate, up, down, k)[0]

    text = jax.jit(layer).lower(
        leaf(rows, d), leaf(n, d), leaf(n, width, d),
        leaf(n, width, d), leaf(n, d, width)).compile().as_text()
    grouped = (rows * k) % 128 == 0 and width % 128 == 0
    assert text.count("tpu_custom_call") == (3 if grouped else 0)


@pytest.mark.parametrize("heads,kv_heads,head_dim,precision", [
    pytest.param(32, 32, 64, None, id="opt-1.3b"),
    pytest.param(16, 4, 128, None, id="gqa"),
    pytest.param(32, 8, 128, "bfloat16", id="gqa-wide"),
    pytest.param(32, 32, 64, "float32", id="opt-1.3b-float32"),
])
def test_paged_read_kernel_compiles_for_v5e(one_chip, heads, kv_heads,
                                            head_dim, precision):
    """``ops.paged_attention``: shapes that ``read_kind`` calls
    ``kernel`` are shapes Mosaic takes (whole lanes, VMEM), and lowered
    for the chip ``decode_attention`` is that kernel, unless the
    caller asked for products above its one bfloat16 pass: then the
    program holds no kernel (``chip_smoke.py``'s serve phase)."""
    from incubator_mxnet_tpu.ops import paged_attention as pa
    slots, block, row = 16, 16, kv_heads * head_dim
    assert pa.read_kind(heads, kv_heads, head_dim, block,
                        "float32") == "kernel"

    def leaf(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                    sharding=one_chip)

    pool = leaf((1025, block, row))
    with jax.default_matmul_precision(precision):
        text = jax.jit(pa.decode_attention).lower(
            leaf((slots, heads, head_dim)), leaf((slots, row)),
            leaf((slots, row)), pool, pool,
            leaf((slots, 128), jnp.int32),
            leaf((slots,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == \
        (0 if precision == "float32" else 1)


@pytest.fixture(scope="module")
def opt_cell(one_chip):
    """``TransformerLM`` at ``benchmark/configs/opt-1.3b.json``'s
    widths, and the engine's arguments at the cell's shapes (16 slots,
    128 blocks of 16 a row, 2049 blocks a pool, 8 layers, float32), all
    abstract: nothing is initialized and nothing is held."""
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    vocab, d, layers, slots, row_blocks, block, blocks = \
        50272, 2048, 8, 16, 128, 16, 2049
    lm = TransformerLM(vocab, d_model=d, n_layers=layers, n_heads=32,
                       max_len=row_blocks * block)

    def leaf(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def vec(n):
        return leaf(n), leaf(n)

    layer = dict(ln1=vec(d), qkv=(leaf(3 * d, d), leaf(3 * d)),
                 proj=(leaf(d, d), leaf(d)), ln2=vec(d),
                 up=(leaf(4 * d, d), leaf(4 * d)),
                 down=(leaf(d, 4 * d), leaf(d)))
    wts = dict(embed=leaf(vocab, d), pos=leaf(row_blocks * block, d),
               ln_f=vec(d), head=leaf(vocab, d),
               layers=[layer] * layers)
    pools = [[leaf(blocks, block, *c["shape"], dtype=c["dtype"])
              for _ in range(layers)] for c in lm._paged_cache()]
    ints = functools.partial(leaf, dtype=jnp.int32)
    return dict(
        lm=lm, wts=wts, pools=pools, layers=layers,
        decode=(lm._build_paged_step(slots, row_blocks, block),
                (ints(slots, row_blocks), ints(slots), ints(slots))),
        prefill=(lm._build_paged_prefill(1024, row_blocks, block),
                 (ints(row_blocks), ints(), ints(1024), ints())))


def _engine_decode(step, slots, wts, groups, ints):
    """The decode program as the engine jits it (``serving.engine``):
    the model's step under the chained loop's entry select, which takes
    each slot's token from the host or from the previous step's
    ``next`` (its shape the step's own), the pools donated; lowered."""
    inner = jax.jit(step)
    nxt = inner.eval_shape(wts, *groups, *ints)[-2]
    on = ints[0].sharding
    return serving.program("decode", serving.chained(inner, slots),
                           len(groups)).lower(
        wts, *groups, *ints,
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=on),
        jax.ShapeDtypeStruct(nxt.shape, nxt.dtype, sharding=on))


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_paged_programs_copy_no_pool_on_v5e(opt_cell, program):
    """The serve cell's programs as the engine jits them (pools
    donated; the decode step under the chained loop's entry select),
    compiled for the chip: the decode step reads through the kernel,
    one a layer; no instruction copies an array of a pool's shape (a
    decode step that copied its pools held 32 such copies of 268.6 MB,
    its temporaries 5.39 GB); every pool that goes in comes out in the
    same buffer; the decode program's temporaries stay under 0.5 GB.
    Counts of a compile, no times."""
    fn, rest = opt_cell[program]
    pools, layers = opt_cell["pools"], opt_cell["layers"]
    if program == "decode":
        lowered = _engine_decode(fn, 16, opt_cell["wts"], pools, rest)
    else:
        lowered = serving.program("prefill_1024", fn, 2).lower(
            opt_cell["wts"], *pools, *rest)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == \
        (layers if program == "decode" else 0)
    shape = ",".join(str(n) for n in pools[0][0].shape)
    copied = [line.strip()[:120] for line in text.splitlines()
              if re.search(rf"\[{shape}\]\S* copy\(", line)]
    assert not copied, copied
    # the module's header: {output index}: (parameter, {}, may-alias)
    aliased = re.search(r"input_output_alias=\{(.*?) \}", text).group(1)
    assert len(re.findall(r"\{\d+\}: \(\d+, \{\}", aliased)) \
        == 2 * layers
    memory = compiled.memory_analysis()
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for pool in pools for a in pool)
    assert memory.alias_size_in_bytes >= held
    if program == "decode":
        assert memory.temp_size_in_bytes < 0.5e9


@pytest.fixture(scope="module")
def latent_cell(one_chip):
    """``LatentMoELM`` at ``benchmark/configs/joyai-llm-flash.json``'s
    widths with its leading dense layer and one routed layer, and the
    engine's arguments at the cell's shapes (64 slots, 512 blocks of 16
    a row, 19,521 blocks of 640 lanes, bfloat16), all abstract."""
    import json

    from incubator_mxnet_tpu.gluon.model_zoo.latent_moe import \
        LatentMoELM
    with open(os.path.join(REPO, "benchmark", "configs",
                           "joyai-llm-flash.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=2)
    lm = LatentMoELM(cfg)
    slots, row_blocks, block, blocks = 64, 512, 16, 19521

    def leaf(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wts = {"embed": leaf(*lm.embed_weight.shape),
           "norm": leaf(*lm.norm.shape),
           "head": leaf(*lm.head_weight.shape),
           "layers": [{k: leaf(*p.shape) for k, p in lw.items()}
                      for lw in lm.layers]}
    spec, = lm._paged_cache()
    pools = [leaf(blocks, block, *spec["shape"]) for _ in lm.layers]
    ints = functools.partial(leaf, dtype=jnp.int32)
    return dict(lm=lm, wts=wts, pools=pools, slots=slots,
                context=row_blocks * block, lanes=spec["shape"][0],
                decode=lm._build_paged_step(slots, row_blocks, block),
                args=(ints(slots, row_blocks), ints(slots), ints(slots)))


def test_latent_decode_reads_through_the_table_on_v5e(latent_cell):
    """The latent cell's decode step as the engine jits it (the pool
    donated, the chained loop's entry select), compiled for the chip: one paged read a layer, one pool
    for keys and values; no instruction holds a slot's allowed context
    (64 x 8192 rows of 640 lanes, 671 MB a layer, the parent's
    gather); every pool comes out in the buffer it went in; the
    temporaries stay under 0.3 GB (the parent's held the gathered
    contexts: 1.34 GB of them for these two layers).  Counts of a
    compile, no times."""
    lm, pools = latent_cell["lm"], latent_cell["pools"]
    assert lm._paged_read(16, "tpu")["read"] == "kernel"
    compiled = _engine_decode(latent_cell["decode"], latent_cell["slots"],
                              latent_cell["wts"], (pools,),
                              latent_cell["args"]).compile()
    text = compiled.as_text()
    reads = [line for line in text.splitlines()
             if "tpu_custom_call" in line
             and "paged_decode_attention" in line]
    assert len(reads) == len(pools), len(reads)
    slots, lanes = latent_cell["slots"], latent_cell["lanes"]
    context = latent_cell["context"]
    held = [line.strip()[:120] for line in text.splitlines()
            if re.search(rf"\[{slots},({context}|{context // 16},16),"
                         rf"{lanes}\]", line)]
    assert not held, held
    aliased = re.search(r"input_output_alias=\{(.*?) \}", text).group(1)
    assert len(re.findall(r"\{\d+\}: \(\d+, \{\}", aliased)) \
        == len(pools)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= sum(
        int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)
    assert memory.temp_size_in_bytes < 0.3e9, memory.temp_size_in_bytes
    # no hyper-connection is built without ``hc_mult``: the parent's
    # kernels (a paged read a layer, the routed layer's three grouped
    # products) and no operation under an ``hc_`` scope
    assert text.count("tpu_custom_call") == len(pools) + 3
    assert "hc_" not in text


def _functions(text):
    """name -> bodies of the functions of a lowered module, with the
    numbers that tell repeated private names apart taken off (two
    lowerings number them differently)."""
    out = {}
    for m in re.finditer(r"^  func\.func \w+ @([\w.$-]+)\(.*?\{\n(.*?)^  \}",
                         text, re.M | re.S):
        out.setdefault(re.sub(r"_\d+$", "", m.group(1)), []).append(
            re.sub(r"(@[A-Za-z_]\w*?)_\d+\b", r"\1", m.group(2)))
    return {name: sorted(bodies) for name, bodies in out.items()}


@pytest.mark.parametrize("cell", ["opt_cell", "latent_cell"])
def test_the_engine_runs_the_models_own_programs_on_v5e(request, cell):
    """What the chained decode loop changes in the device's work, at
    the serve cells' shapes lowered for the chip: the decode program
    is the model's own step, called as it is (its body the text of the
    step lowered alone, every function under it the same) behind one
    select at the entry; a prefill program is the text of the model's
    own prefill lowered alone.  Both are lowered in one process, so
    the kernels' bodies carry the same source lines."""
    c = request.getfixturevalue(cell)
    wts = c["wts"]
    if cell == "opt_cell":
        (step, ints), (prefill, rest) = c["decode"], c["prefill"]
        groups, slots = tuple(c["pools"]), 16
    else:
        step, ints, groups, slots = (c["decode"], c["args"],
                                     (c["pools"],), c["slots"])
        prefill = c["lm"]._build_paged_prefill(1024, 512, 16)
        leaf = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                                 sharding=ints[0].sharding)
        rest = (leaf((512,)), leaf(()), leaf((1024,)), leaf(()))
    donate = tuple(range(1, 1 + len(groups)))
    alone = _functions(jax.jit(step, donate_argnums=donate).lower(
        wts, *groups, *ints).as_text())
    served = _functions(_engine_decode(step, slots, wts, groups,
                                       ints).as_text())
    assert served.pop("step") == alone.pop("main")
    entry, = served.pop("main")
    assert served == alone
    ops = [re.search(r"= (\S+)", line).group(1)
           for line in entry.splitlines() if " = " in line]
    assert ops == ["stablehlo.slice", "stablehlo.select", "call"], ops
    assert "call @step(" in entry
    alone = jax.jit(prefill, donate_argnums=donate).lower(wts, *groups,
                                                          *rest)
    served = serving.program("prefill_1024", prefill,
                             len(groups)).lower(wts, *groups, *rest)
    assert _functions(served.as_text()) == _functions(alone.as_text())


def _abstract_latent(lm, leaf, blocks):
    """A ``LatentMoELM``'s weights and pools as abstract leaves."""
    wts = {"embed": leaf(*lm.embed_weight.shape),
           "norm": leaf(*lm.norm.shape),
           "head": leaf(*lm.head_weight.shape),
           "layers": [{k: leaf(*p.shape) for k, p in lw.items()}
                      for lw in lm.layers]}
    spec, = lm._paged_cache()
    return wts, [leaf(blocks, 16, *spec["shape"]) for _ in lm.layers]


def test_hyper_connected_decode_compiles_for_v5e(one_chip):
    """``LatentMoELM`` at ``benchmark/configs/xing4.0-29b-a4b.json``:
    its 6-layer decode step at the cell's shapes (64 slots, 512 blocks
    of 16 a row, 19,521 blocks of 640 lanes, bfloat16) as the engine
    jits it (the chained loop's entry select), for the chip: one paged read a layer, every pool out in
    the buffer it came in, temporaries under 0.1 GB (the streams of 64
    tokens are 7 MB); 12 hyper-connections, each with the same count
    of operations under ``hc_map`` (the mapping and gates in the
    program's body, the Sinkhorn rounds in a loop of 4 passes of 5)
    and under ``hc_mix``.  Counts of a compile, no times."""
    import json

    from incubator_mxnet_tpu.gluon.model_zoo.latent_moe import \
        LatentMoELM
    with open(os.path.join(REPO, "benchmark", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        lm = LatentMoELM(json.load(f))

    def leaf(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    wts, pools = _abstract_latent(lm, leaf, 19521)
    ints = functools.partial(leaf, dtype=jnp.int32)
    compiled = _engine_decode(lm._build_paged_step(64, 512, 16), 64, wts,
                              (pools,), (ints(64, 512), ints(64),
                                         ints(64))).compile()
    text = compiled.as_text()
    reads = [line for line in text.splitlines()
             if "tpu_custom_call" in line
             and "paged_decode_attention" in line]
    assert len(reads) == len(pools) == 6
    aliased = re.search(r"input_output_alias=\{(.*?) \}", text).group(1)
    assert len(re.findall(r"\{\d+\}: \(\d+, \{\}", aliased)) == 6
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.1e9, memory.temp_size_in_bytes
    entry = text[text.index("\nENTRY"):]
    ops = [line for line in entry.splitlines() if " = " in line
           and not re.search(r" (parameter|get-tuple-element|tuple|"
                             r"constant|bitcast)\(", line)]
    loops = [line for line in ops if " while(" in line
             and "/hc_map/" in line]
    assert len(loops) == 12
    # a pass of 5 rounds is some two fusions a round (10 when this was
    # written); around the loop, some 11 operations a hyper-connection
    # map and gate (133 for 12) and 3 or 4 mix (44)
    body = re.search(r"body=%?([\w.\-]+)", loops[0]).group(1)
    body = re.search(r"\n%?" + re.escape(body) + r" .*?\n}", text,
                     re.S).group(0)
    assert len([line for line in body.splitlines()[1:] if " = " in line
                and not re.search(r" (parameter|get-tuple-element|"
                                  r"tuple|constant|bitcast)\(", line)]) \
        <= 12
    assert 0 < sum(1 for line in ops if "/hc_map/" in line) <= 12 * 12
    assert 0 < sum(1 for line in ops if "/hc_mix/" in line) <= 12 * 4


def test_rtc_example_kernel_compiles_for_v5e(one_chip):
    """examples/custom_pallas_kernel.py's kernel through
    ``rtc.compile_kernel``: the compiled-or-interpreted choice follows
    the platform the call is lowered for, not the default backend
    (which is the CPU here)."""
    spec = importlib.util.spec_from_file_location(
        "custom_pallas_kernel_example",
        os.path.join(REPO, "examples", "custom_pallas_kernel.py"))
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)    # registers scale_shift_relu
    try:
        x = jax.ShapeDtypeStruct((512, 1024), jnp.float32,
                                 sharding=one_chip)
        compiled = jax.jit(
            lambda a: example.fused(a, alpha=2.0, beta=0.5)).lower(
            x).compile()
        assert "tpu_custom_call" in compiled.as_text()
    finally:
        rtc.unregister("scale_shift_relu")


@pytest.mark.parametrize("graph,accum", [
    ("mlp", 1), ("mlp", 2),
    ("resnet_block", 1), ("resnet_block", 2),
    ("transformer_step", 1),
])
def test_planner_never_under_the_v5e_compiler(one_chip, graph, accum):
    """The planner plans for the TPU, so the TPU's compiler is what it
    answers to (tests/test_memory_planner.py holds it to XLA:CPU,
    whose accounting moves with the installed XLA).  What the OOM gate
    needs is one-sided: a plan below the compiler's live bytes would
    wave through a step that cannot fit.  The other side is not
    banded.  At these toy sizes the v5e compiler reports no temporary
    HBM at all, and at the smoke's real sizes the plan is about twice
    its number (ResNet-50 B=32: 3.94 vs 1.48 GiB; the 150M LM step:
    13.47 vs 6.76 GiB — PERF.md, ISSUE 21; compiles, not chip runs)."""
    s, shapes = getattr(_graphs, f"_graph_{graph}")(symmod)
    inputs = GRAPH_INPUTS[graph]
    compiled = _train_compiled(s, shapes, inputs, grad_accum=accum,
                               sharding=one_chip)
    tpu = mp.xla_live_bytes(compiled.memory_analysis())
    assert tpu, "the v5e compiler reports a memory analysis"
    plan = mp.plan_memory(s, shapes, input_names=inputs,
                          grad_accum=accum, donate=True)
    assert plan.total() >= tpu, (
        f"{graph} accum={accum}: planner {plan.total():.0f} under the "
        f"v5e compiler's {tpu:.0f} — {plan.describe()}")


@pytest.fixture(scope="module")
def nemotron_cell(one_chip):
    """``NemotronHLM`` at ``benchmark/configs/nemotron-3-super-120b-a12b
    .json``'s widths and 11 layers (128 of 512 experts held), and the
    engine's pools at the cell's shapes (128 slots, 512 blocks of 16 a
    row, 39,041 blocks; the attention layer's keys and values, each
    Mamba layer's state and conv tail a slot), all abstract, bfloat16
    where the weights are."""
    import json

    from incubator_mxnet_tpu.gluon.model_zoo.nemotron_h import \
        NemotronHLM
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron-3-super-120b-a12b.json")) as f:
        cfg = json.load(f)
    lm = NemotronHLM(dict(cfg, n_routed_experts=512),
                     held=(0, cfg["n_routed_experts"]))
    lm.cast("bfloat16")

    def leaf(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    wts = {"embed": leaf(*lm.embed_weight.shape),
           "norm": leaf(*lm.norm.shape),
           "head": leaf(*lm.head_weight.shape),
           "layers": [{k: leaf(*p.shape) for k, p in lw.items()}
                      for lw in lm.layers]}
    slots, row_blocks, blocks = 128, 512, 39041
    pools = tuple(
        [leaf(*(((slots,) if c.get("per") == "slot" else (blocks, 16))
                + tuple(c["shape"])), dtype=jnp.dtype(c["dtype"]))
         for _ in c["layers"]] for c in lm._paged_cache())
    ints = functools.partial(leaf, dtype=jnp.int32)
    return dict(lm=lm, wts=wts, pools=pools, slots=slots,
                decode=lm._build_paged_step(slots, row_blocks, 16),
                args=(ints(slots, row_blocks), ints(slots), ints(slots)),
                prefill=lm._build_paged_prefill(4096, row_blocks, 16),
                rest=(ints(row_blocks), ints(), ints(4096), ints(),
                      ints()))


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_hybrid_programs_compile_for_v5e(nemotron_cell, program):
    """The Nemotron cell's decode program (as the engine jits it) and
    its 4096-row prefill, at the published widths, compiled for the
    chip: the decode step holds one ``ssm_state_step`` kernel a Mamba
    layer (5), one paged read (the attention layer) and the grouped
    product's two kernels a routed layer (10); no instruction copies a
    state pool; every pool comes out in the buffer it went in (13.35 GB
    of arguments: 9.30 of weights, 4.04 of pools); the decode program's
    temporaries stay under 0.1 GB, the prefill's under 2.5 GB.  Counts
    of a compile, no times."""
    c = nemotron_cell
    pools = c["pools"]
    if program == "decode":
        lowered = _engine_decode(c["decode"], c["slots"], c["wts"], pools,
                                 c["args"])
    else:
        lowered = serving.program("prefill_4096", c["prefill"],
                                  len(pools)).lower(c["wts"], *pools,
                                                    *c["rest"])
    compiled = lowered.compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line]

    def named(name):
        return sum(1 for line in kernels if name in line)
    if program == "decode":
        assert (named("ssm_state_step"), named("paged_decode_attention"),
                len(kernels)) == (5, 1, 16)
    else:
        assert named("ssm_state_step") == 0 and len(kernels) == 10
    state = ",".join(str(n) for n in pools[2][0].shape)
    copied = [line.strip()[:120] for line in text.splitlines()
              if re.search(rf"\[{state}\]\S* copy\(", line)]
    assert not copied, copied
    aliased = re.search(r"input_output_alias=\{(.*?) \}", text).group(1)
    assert len(re.findall(r"\{\d+\}: \(\d+, \{\}", aliased)) \
        == sum(len(p) for p in pools) == 12
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for p in pools for a in p)
    assert memory.temp_size_in_bytes < (
        0.1e9 if program == "decode" else 2.5e9), \
        memory.temp_size_in_bytes
