"""The paged decode read (incubator_mxnet_tpu/ops/paged_attention.py)
and how ``TransformerLM``'s paged programs touch the cache: the Pallas
kernel in interpret mode against the plain read, the write that no
read depends on, the engine's token-for-token equality with
``generate()``, and the counters of live and allowed blocks.  What the
TPU's compiler makes of the same programs is
``tests/test_tpu_compile.py``'s."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import telemetry, tracing
from incubator_mxnet_tpu.gluon.model_zoo.transformer import (
    TransformerLM)
from incubator_mxnet_tpu.ops import paged_attention as pa
from incubator_mxnet_tpu.serving import ServingEngine

VOCAB = 41
BS = 8                          # block size of the kernel cases
MB = 6                          # blocks a table row holds: 48 positions


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _case(heads, kv_heads, head_dim, n_past, seed=0, blocks=64,
          block=BS, dtype="float32", shared=False):
    """Random operands; every slot's row is a scrambled draw of block
    ids that covers positions 0 .. n_past, scratch (0) behind it.
    ``shared``: one pool for keys and values, the step's own row the
    same for both."""
    rng = np.random.RandomState(seed)
    b, row = len(n_past), kv_heads * head_dim

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape), dtype)

    ids = rng.permutation(np.arange(1, blocks))
    tables = np.zeros((b, MB), np.int32)
    at = 0
    for i, n in enumerate(n_past):
        held = n // block + 1
        tables[i, :held] = ids[at:at + held]
        at += held
    q, kn, vn = draw(b, heads, head_dim), draw(b, row), draw(b, row)
    kp, vp = draw(blocks, block, row), draw(blocks, block, row)
    if shared:
        vn, vp = kn, kp
    return (q, kn, vn, kp, vp, jnp.asarray(tables),
            jnp.asarray(n_past, jnp.int32))


# an inactive slot, one cached row, a block's last row, a block's
# first row, a ragged middle, the full row
RAGGED = (0, 1, BS - 1, BS, 2 * BS + 3, MB * BS - 1)


@pytest.mark.parametrize("heads,kv_heads,head_dim,block,dtype,shared,scale", [
    pytest.param(8, 8, 16, BS, "float32", False, None, id="mha"),
    pytest.param(8, 2, 64, BS, "float32", False, None, id="gqa"),
    pytest.param(16, 4, 32, BS, "float32", False, None, id="gqa-4x"),
    pytest.param(8, 8, 16, BS, "float32", True, None, id="one-pool"),
    pytest.param(8, 2, 64, 16, "bfloat16", False, None, id="bfloat16"),
    pytest.param(16, 1, 384, BS, "float32", False, None,
                 id="one-kv-head"),
    pytest.param(8, 2, 64, BS, "float32", False, 0.3, id="scale"),
    pytest.param(32, 1, 640, 16, "bfloat16", True, 192 ** -0.5,
                 id="latent"),
])
def test_kernel_reads_what_the_plain_read_reads(heads, kv_heads,
                                                head_dim, block, dtype,
                                                shared, scale):
    """Grouped heads, one pool for keys and values, a bfloat16 pool in
    whole 16-row tiles, one kv head under a row of several lanes (the
    query as it comes), the caller's scale: one kernel."""
    assert pa.read_kind(heads, kv_heads, head_dim, block,
                        dtype) == "kernel"
    n_past = (0, 1, block - 1, block, 2 * block + 3, MB * block - 1)
    args = _case(heads, kv_heads, head_dim, n_past, block=block,
                 dtype=dtype, shared=shared)
    got = pa.kernel_read(*args, scale=scale, interpret=True)
    q, kn, vn, kp, vp, tables, n = args
    # the kernel's products take bfloat16 operands and add in float32
    # (a TPU's default precision); the plain read on the CPU is exact,
    # so it is given the rounded operands.  What is left is the
    # rounding of the softmax's weights before the second product
    want = pa.plain_read(_bf16(q), _bf16(kn), _bf16(vn), _bf16(kp),
                         vp if shared else _bf16(vp), tables, n, scale)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    exact = pa.plain_read(*args, scale)
    np.testing.assert_allclose(got, exact, rtol=5e-2, atol=5e-2)
    # the inactive slot attends to its own row alone
    np.testing.assert_allclose(
        got[0].reshape(heads, head_dim),
        jnp.repeat(vn[0].astype(jnp.float32).reshape(kv_heads, head_dim),
                   heads // kv_heads, axis=0), rtol=0, atol=1e-6)
    if shared:
        # a block copied once and read by both products: the numbers
        # of two pools that hold the same rows
        assert vp is kp and vn is kn
        np.testing.assert_array_equal(
            got, pa.kernel_read(q, kn, kn + 0, kp, kp + 0, tables, n,
                                scale=scale, interpret=True))
    if scale is not None:
        assert float(jnp.max(jnp.abs(
            got - pa.kernel_read(*args, interpret=True)))) > 1e-2


@pytest.mark.parametrize("read,garbage,dtype,shared", [
    pytest.param("kernel", np.nan, "float32", False, id="kernel-nan"),
    pytest.param("plain", 1e30, "float32", False, id="plain-1e+30"),
    pytest.param("kernel", np.nan, "float32", True,
                 id="kernel-nan-one-pool"),
    pytest.param("plain", 1e30, "float32", True,
                 id="plain-1e+30-one-pool"),
    pytest.param("kernel", np.nan, "bfloat16", True,
                 id="kernel-nan-one-pool-bfloat16"),
    pytest.param("plain", 1e30, "bfloat16", True,
                 id="plain-1e+30-one-pool-bfloat16"),
])
def test_nothing_behind_n_past_reaches_the_output(read, garbage, dtype,
                                                  shared):
    block = 16 if dtype == "bfloat16" else BS
    n_past = (0, 1, block - 1, block, 2 * block + 3, 3 * block)
    q, kn, vn, kp, vp, tables, n = _case(8, 8, 16, n_past, seed=1,
                                         block=block, dtype=dtype,
                                         shared=shared)
    fn = functools.partial(pa.kernel_read, interpret=True) \
        if read == "kernel" else pa.plain_read
    clean = fn(q, kn, vn, kp, vp, tables, n)
    # garbage in every cached position no slot may see: the rows
    # behind n_past in a slot's last block (the row at n_past is the
    # step's own, not yet in the pool), every block no slot holds,
    # scratch.  The kernel leaves such rows out (NaN goes nowhere);
    # the plain read gives them the weight 0, as it always has.  In
    # one pool for keys and values they are neither
    dead = np.ones(kp.shape[:2], bool)
    for row, upto in zip(np.asarray(tables), n_past):
        for pos in range(upto):
            dead[row[pos // block], pos % block] = False
    poison = jnp.where(jnp.asarray(dead)[:, :, None], garbage, 0.0)
    kd = (kp + poison).astype(dtype)
    vd = kd if shared else (vp + poison).astype(dtype)
    dirty = fn(q, kn, vn, kd, vd, tables, n)
    assert np.isfinite(np.asarray(dirty)).all()
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))


def test_table_order_is_the_context_order():
    """The same context under another assignment of block ids reads
    the same; the same ids in another order do not (attention itself
    has no order: what turns on it is which rows lie behind n_past,
    so the row's last, partly live block changes place)."""
    q, kn, vn, kp, vp, tables, n = _case(8, 8, 16, (3 * BS + 2,) * 2,
                                         seed=2)
    read = functools.partial(pa.kernel_read, interpret=True)
    base = read(q, kn, vn, kp, vp, tables, n)
    perm = np.random.RandomState(3).permutation(kp.shape[0])
    moved = read(q, kn, vn, kp[perm], vp[perm],
                 jnp.asarray(np.argsort(perm)[np.asarray(tables)]), n)
    np.testing.assert_allclose(moved, base, rtol=0, atol=1e-6)
    swapped = tables.at[:, 0].set(tables[:, 3]).at[:, 3].set(
        tables[:, 0])
    assert float(jnp.max(jnp.abs(
        read(q, kn, vn, kp, vp, swapped, n) - base))) > 1e-2


@pytest.mark.parametrize("heads,kv_heads,head_dim,block,dtype,kind", [
    (32, 32, 64, 16, "float32", "kernel"),      # the OPT cell
    (16, 4, 128, 16, "float32", "kernel"),
    (4, 4, 8, 4, "float32", "plain"),           # a row of 32 lanes
    (4, 2, 64, 8, "float32", "plain"),          # heads no whole tile
    (8, 8, 16, 4, "float32", "plain"),          # a block of 4 rows
    (8, 8, 16, 16, "bfloat16", "kernel"),       # whole 16-row tiles
    (8, 8, 16, 8, "int8", "plain"),
    (64, 64, 128, 16, "float32", "plain"),      # (64, 8192) no VMEM
    (8, 8, 16, 8, "bfloat16", "plain"),         # half a bfloat16 tile
    (32, 1, 640, 16, "bfloat16", "kernel"),     # the latent cell
    (32, 1, 640, 8, "bfloat16", "plain"),
])
def test_shapes_decide_the_read(heads, kv_heads, head_dim, block,
                                dtype, kind):
    assert pa.read_kind(heads, kv_heads, head_dim, block, dtype) == kind
    # lowered for anything but a TPU the plain read stands in
    assert pa.read_kind(heads, kv_heads, head_dim, block, dtype,
                        "cpu") == "plain"


@pytest.mark.parametrize("precision,kind", [
    (None, "kernel"), ("default", "kernel"), ("bfloat16", "kernel"),
    ("float32", "plain"), ("highest", "plain"), ("high", "plain"),
    ("tensorfloat32", "plain"),
])
def test_the_precision_in_force_decides_the_read(precision, kind):
    """The kernel's products are one bfloat16 pass: a caller who asked
    for more (``chip_smoke.py``'s serve phase holds the engine to
    ``generate()`` under ``float32``) gets the plain read, which XLA
    computes at the precision asked for."""
    with jax.default_matmul_precision(precision):
        assert pa.read_kind(32, 32, 64, 16, "float32") == kind


@pytest.mark.parametrize("n_past,rows", [(0, 1), (5, 1), (47, 1),
                                         (0, 16), (11, 32), (40, 16)])
def test_gathered_context_lays_the_rows_over_n_past(n_past, rows):
    """What both the prefill (S rows) and the plain decode read (one)
    attend over: the table row's positions in order, the program's own
    rows from ``n_past`` on, what passes the row's end dropped."""
    rng = np.random.RandomState(9)
    pool = jnp.asarray(rng.randn(16, BS, 24), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, 16))[:MB],
                        jnp.int32)
    new = jnp.asarray(rng.randn(rows, 24), jnp.float32)
    got = np.asarray(pa.gathered_context(pool, table, jnp.int32(n_past),
                                         new))
    want = np.asarray(pool)[np.asarray(table)].reshape(MB * BS, 24)
    upto = min(n_past + rows, MB * BS)
    want[n_past:upto] = np.asarray(new)[:upto - n_past]
    np.testing.assert_array_equal(got, want)


def test_a_shape_the_kernel_cannot_tile_takes_the_plain_read():
    args = _case(4, 4, 8, (0, 5, 17), seed=4)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(pa.decode_attention)(*args)),
        np.asarray(jax.jit(pa.plain_read)(*args)))


# ------------------------------------------------- the model's programs
def _lm(**kw):
    cfg = dict(d_model=128, n_layers=2, n_heads=8, max_len=MB * BS)
    cfg.update(kw)
    mx.random.seed(0)
    net = TransformerLM(VOCAB, **cfg)
    net.initialize(mx.initializer.Normal(0.08))
    return net


MODELS = [pytest.param(dict(), id="learned-mha"),
          pytest.param(dict(pos="rope", n_kv_heads=4, d_model=256),
                       id="rope-gqa")]


def _engine_state(net, prompts, new):
    """An engine that has prefilled ``prompts`` and decoded ``new``
    steps: its weights, pools, and the next step's arguments."""
    eng = ServingEngine(net, max_batch=len(prompts) + 1, block_size=BS,
                        num_blocks=32, prefix_cache=False)
    reqs = [eng.submit(p, new + 4) for p in prompts]
    for _ in range(new):
        eng.step()
    b = eng.max_batch
    tables = np.zeros((b, eng.max_blocks), np.int32)
    n_past, tokens = np.zeros(b, np.int32), np.zeros(b, np.int32)
    for r in reqs:
        tables[r.slot, :len(r.block_ids)] = r.block_ids
        n_past[r.slot], tokens[r.slot] = r.n_past, r.generated[-1]
    return eng, jnp.asarray(tables), jnp.asarray(n_past), \
        jnp.asarray(tokens)


@pytest.mark.parametrize("cfg", MODELS)
def test_step_with_the_kernel_equals_step_with_the_plain_read(
        cfg, monkeypatch):
    """The whole decode step, RoPE and grouped heads included: the
    kernel (interpreted) in the plain read's place gives the same
    pools to the last bit and the same logits to the products'
    rounding; the last slot is inactive."""
    net = _lm(**cfg)
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(1, VOCAB, n)) for n in (BS - 1, 19, 3)]
    eng, tables, n_past, tokens = _engine_state(net, prompts, 2)
    build = functools.partial(net._build_paged_step, eng.max_batch,
                              eng.max_blocks, BS)
    plain = jax.jit(build())
    monkeypatch.setattr(pa, "decode_attention", functools.partial(
        pa.kernel_read, interpret=True))
    kernel = jax.jit(build())
    *pools_p, nxt_p, logits_p = plain(eng._wts, *eng._pools, tables,
                                      n_past, tokens)
    *pools_k, nxt_k, logits_k = kernel(eng._wts, *eng._pools, tables,
                                       n_past, tokens)
    live = np.asarray(n_past) > 0
    assert live.sum() == 3 and not live[-1]
    np.testing.assert_allclose(np.asarray(logits_k)[live],
                               np.asarray(logits_p)[live],
                               rtol=0, atol=3e-2)
    assert np.abs(np.asarray(logits_p)[live]).max() > 0.5
    # the first layer's rows are written before any read differs
    for got, want in zip(pools_k, pools_p):
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        np.testing.assert_allclose(np.asarray(got[1]),
                                   np.asarray(want[1]),
                                   rtol=0, atol=3e-2)


@pytest.mark.parametrize("cfg", MODELS)
def test_the_step_writes_one_row_a_slot_and_nothing_else(cfg):
    net = _lm(**cfg)
    rng = np.random.RandomState(6)
    prompts = [list(rng.randint(1, VOCAB, n)) for n in (BS, 13)]
    eng, tables, n_past, tokens = _engine_state(net, prompts, 1)
    step = jax.jit(net._build_paged_step(eng.max_batch, eng.max_blocks,
                                         BS))
    before = [[np.asarray(a) for a in pool] for pool in eng._pools]
    *after, _, _ = step(eng._wts, *eng._pools, tables, n_past, tokens)
    tables, n_past = np.asarray(tables), np.asarray(n_past)
    written = {(int(tables[i, n // BS]), int(n % BS))
               for i, n in enumerate(n_past)}
    assert (0, 0) in written            # the inactive slot: scratch
    for pool_b, pool_a in zip(before, after):
        for was, now in zip(pool_b, pool_a):
            changed = np.argwhere(
                np.any(np.asarray(now) != was, axis=-1))
            assert {tuple(c) for c in changed} <= written
            assert len(changed) >= 2    # both live slots' rows


@pytest.mark.parametrize("cfg", MODELS)
def test_suffix_prefill_behind_an_unaligned_past_equals_one_prefill(
        cfg):
    """``n_past`` in the middle of a block: the suffix's rows are laid
    over the gathered context where they belong, clamped nowhere."""
    net = _lm(**cfg)
    eng = ServingEngine(net, max_batch=2, block_size=BS, num_blocks=32,
                        prefix_cache=False)
    toks = np.random.RandomState(7).randint(1, VOCAB, 29)
    table = jnp.asarray(np.r_[[5, 9, 2, 7], np.zeros(MB - 4)], jnp.int32)

    def prefill(pools, n_past, suffix, bucket):
        fn = jax.jit(net._build_paged_prefill(bucket, MB, BS))
        padded = np.zeros(bucket, np.int32)
        padded[:len(suffix)] = suffix
        *pools, nxt, logits = fn(eng._wts, *pools, table,
                                 np.int32(n_past), jnp.asarray(padded),
                                 np.int32(len(suffix)))
        return pools, int(nxt), np.asarray(logits)

    whole, nxt_w, logits_w = prefill(eng._pools, 0, toks, 32)
    first, _, _ = prefill(eng._pools, 0, toks[:11], 16)
    # the top bucket: n_past + bucket passes the table row's 48
    both, nxt_b, logits_b = prefill(first, 11, toks[11:], 32)
    assert nxt_b == nxt_w
    np.testing.assert_allclose(logits_b, logits_w, rtol=0, atol=2e-5)
    held = np.asarray(table)[:4]
    for pool_w, pool_b in zip(whole, both):
        for a, b in zip(pool_w, pool_b):
            np.testing.assert_allclose(
                np.asarray(b)[held].reshape(4 * BS, -1)[:29],
                np.asarray(a)[held].reshape(4 * BS, -1)[:29],
                rtol=0, atol=2e-5)


def _generate(net, prompt, new):
    out = net.generate(
        mx.nd.array(np.asarray([prompt], np.int32)), new)
    return [int(t) for t in out.asnumpy()[0]][len(prompt):]


@pytest.mark.parametrize("cfg", MODELS)
def test_engine_serves_what_generate_gives_token_for_token(cfg):
    """Prefill, then decode steps over slots that come and go, a
    prefix hit among them: on the CPU the plain read is built (the
    engine's event says so, and the platform) and every request's
    tokens are ``generate()``'s."""
    net = _lm(**cfg)
    before = len(tracing.events("serve_paged_read"))
    eng = ServingEngine(net, max_batch=3, block_size=BS, num_blocks=40,
                        prefix_cache=True)
    rng = np.random.RandomState(8)
    shared = list(rng.randint(1, VOCAB, 2 * BS))
    prompts = [list(rng.randint(1, VOCAB, n)) for n in (3, 17, BS, 30)] \
        + [shared + [4, 5], shared + [6]]
    reqs = [eng.submit(p, n) for p, n in zip(prompts, (6, 9, 5, 7, 4, 6))]
    eng.run()
    for req, prompt in zip(reqs, prompts):
        assert req.generated == _generate(net, prompt,
                                          req.max_new_tokens), prompt
    assert eng.trace_counts["decode"] == 1
    built = tracing.events("serve_paged_read")[before:]
    assert len(built) == 1
    heads, kv = net.n_heads, net.n_kv_heads
    assert {k: built[0][k] for k in (
        "read", "platform", "max_batch", "max_blocks", "block_size",
        "heads", "kv_heads", "head_dim", "dtype")} == dict(
        read="plain", platform="cpu", max_batch=3, max_blocks=MB,
        block_size=BS, heads=heads, kv_heads=kv,
        head_dim=net._d // heads, dtype="float32")
    # the same shapes lowered for a TPU go through the kernel, unless
    # the caller asked for more than its one bfloat16 pass
    assert net._paged_read(BS, "tpu")["read"] == "kernel"
    with jax.default_matmul_precision("float32"):
        assert net._paged_read(BS, "tpu")["read"] == "plain"
    # the pools are what the model described: a row of whole lanes
    assert eng.cache_spec[0]["shape"] == (kv * (net._d // heads),)


def test_a_model_of_narrow_rows_says_plain_on_every_platform():
    mx.random.seed(0)
    net = TransformerLM(VOCAB, d_model=32, n_layers=1, n_heads=4,
                        max_len=32)
    net.initialize(mx.initializer.Xavier())
    before = len(tracing.events("serve_paged_read"))
    eng = ServingEngine(net, max_batch=2, block_size=4, num_blocks=16)
    req = eng.submit([1, 2, 3], 3)
    eng.run()
    assert req.generated == _generate(net, [1, 2, 3], 3)
    assert [e["read"] for e in
            tracing.events("serve_paged_read")[before:]] == ["plain"]
    assert net._paged_read(4, "tpu")["read"] == "plain"


def test_live_over_allowed_blocks_on_a_known_schedule():
    """One request of 5 prompt tokens and 4 new ones in blocks of 4,
    8 blocks allowed: the prefill gives the first token, three decode
    steps follow at n_past 5, 6, 7 (two blocks each); then a second
    request joins at n_past 9 (three blocks) beside none."""
    net = _lm()
    eng = ServingEngine(net, max_batch=2, block_size=4, num_blocks=40,
                        max_len=32, prefix_cache=False)
    assert eng.max_blocks == 8

    def value(name):
        return telemetry.get_registry().counter(
            f"serving_decode_blocks_{name}_total").value

    live0, allowed0 = value("live"), value("allowed")
    eng.submit([1, 2, 3, 4, 5], 4)
    eng.run()
    assert value("live") - live0 == 2 + 2 + 2
    assert value("allowed") - allowed0 == 3 * 8
    live0, allowed0 = value("live"), value("allowed")
    eng.submit(list(range(1, 10)), 2)       # one step at n_past 9
    eng.submit([7, 8], 3)                   # two at n_past 2, 3
    eng.run()
    assert value("live") - live0 == (3 + 1) + 1
    assert value("allowed") - allowed0 == 2 * 8 + 1 * 8
    assert 0 < (value("live") - live0) / (value("allowed") - allowed0) \
        < 0.25
