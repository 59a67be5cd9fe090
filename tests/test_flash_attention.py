"""Flash attention Pallas kernel (ops/flash.py): forward numerics
against the XLA oracle, gradients through the custom VJP, registry
integration with the autograd tape, and model integration.

Runs in Pallas interpret mode on the CPU mesh; the same kernel
compiles via Mosaic on TPU.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, nd
from incubator_mxnet_tpu.ops.flash import (_reference_attention,
                                           flash_attention)


def _rand(bh, l, d, seed=0):
    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.normal(0, 1, (bh, l, d)), jnp.float32)
            for _ in range(3)]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("l", [128, 256])
def test_forward_matches_reference(causal, l):
    q, k, v = _rand(2, l, 64)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _reference_attention(q, k, v, causal, 1.0 / 8.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_multiple_key_tiles_online_softmax():
    # L=256 with 128-tiles forces >1 inner iteration: the running
    # max/denominator rescaling is actually exercised
    q, k, v = _rand(1, 256, 32, seed=3)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _reference_attention(q, k, v, True, 1.0 / math.sqrt(32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gradients_match_reference():
    q, k, v = _rand(2, 128, 32, seed=1)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(
            q, k, v, True, 1.0 / math.sqrt(32)) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_unsupported_shape_falls_back():
    # L=200 is untileable (200 % 128 != 0): must take the XLA
    # reference fallback, preserving causal flag and scale — and say
    # so, since it is not the kernel the caller asked for
    from incubator_mxnet_tpu.ops import flash as flash_mod
    q, k, v = _rand(1, 200, 16)
    assert not flash_mod._supported(q, k)
    with pytest.warns(UserWarning, match="not tiled by 128"):
        out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _reference_attention(q, k, v, True, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_registry_op_and_tape():
    # the op is on the nd namespace and records on the autograd tape
    rs = np.random.RandomState(0)
    q = nd.array(rs.normal(0, 1, (2, 128, 16)).astype("float32"))
    k = nd.array(rs.normal(0, 1, (2, 128, 16)).astype("float32"))
    v = nd.array(rs.normal(0, 1, (2, 128, 16)).astype("float32"))
    for t in (q, k, v):
        t.attach_grad()
    with autograd.record():
        out = nd._internal._flash_attention(q, k, v, causal=True,
                                            interpret=True)
        s = (out * out).sum()
    s.backward()
    ref = _reference_attention(q._data, k._data, v._data, True,
                               0.25)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert float(np.abs(q.grad.asnumpy()).max()) > 0
    assert float(np.abs(k.grad.asnumpy()).max()) > 0


def test_model_uses_flash(monkeypatch):
    # MXTPU_FLASH=1 routes CausalSelfAttention through the kernel and
    # must reproduce the default path's logits
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    mx.random.seed(0)
    net = TransformerLM(37, d_model=32, n_layers=2, n_heads=4,
                        max_len=128)
    net.initialize(mx.initializer.Xavier())
    toks = mx.nd.array(np.random.RandomState(0)
                       .randint(0, 37, (2, 128)).astype("int32"))
    ref = net(toks).asnumpy()
    monkeypatch.setenv("MXTPU_FLASH", "1")
    from incubator_mxnet_tpu.ops import registry
    calls = []
    op = registry.OPS["_flash_attention"]
    orig_fn = op.fn

    def spy(*a, **kw):
        calls.append(1)
        return orig_fn(*a, **kw)

    monkeypatch.setattr(op, "fn", spy)
    got = net(toks).asnumpy()
    assert calls, "flash path never engaged despite MXTPU_FLASH=1"
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_eager_forward_on_cpu_operands_interprets_on_a_tpu_host(
        monkeypatch):
    """On a host with a TPU the default backend is ``tpu`` while a
    shape-settling eager forward (parallel.functionalize) may run on
    CPU-placed operands.  The kernel's compiled-or-interpreted choice
    must follow where the call is lowered, not the default backend:
    steered here by saying the default backend is a TPU (at the
    parent commit this raised "Only interpret mode is supported on
    CPU backend")."""
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    mx.random.seed(0)
    net = TransformerLM(37, d_model=32, n_layers=1, n_heads=2,
                        max_len=128)
    net.initialize(mx.initializer.Xavier())
    toks = mx.nd.array(np.random.RandomState(0)
                       .randint(0, 37, (2, 128)).astype("int32"))
    ref = net(toks).asnumpy()               # XLA attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert net.blocks[0].attn._use_flash()
    assert toks._data.devices() == {jax.devices("cpu")[0]}
    got = net(toks).asnumpy()               # the kernel, interpreted
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    # ... and shape settling through functionalize does the same
    from incubator_mxnet_tpu import parallel
    pure = parallel.functionalize(net, toks)
    outs, _ = pure.apply(pure.params(), pure.states(), [toks._data],
                         jax.random.PRNGKey(0), training=False)
    np.testing.assert_allclose(np.asarray(outs[0]), ref, rtol=1e-4,
                               atol=1e-4)


def test_flash_under_a_dp_mesh_matches_one_device(monkeypatch):
    """Under a multi-device mesh the compiled step runs the kernel
    through shard_map (GSPMD cannot partition a Mosaic kernel; the
    v5e compile is in test_tpu_compile.py): dp=4 must train like one
    device."""
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.gluon.model_zoo import transformer as tr
    monkeypatch.setenv("MXTPU_FLASH", "1")
    calls = []
    real = tr._flash_on_mesh
    monkeypatch.setattr(
        tr, "_flash_on_mesh",
        lambda *a: calls.append(a[3].shape["dp"]) or real(*a))
    mx.random.seed(0)
    net = tr.TransformerLM(37, d_model=32, n_layers=1, n_heads=2,
                           max_len=128)
    net.initialize(mx.initializer.Xavier())
    rs = np.random.RandomState(0)
    toks = rs.randint(0, 37, (4, 128)).astype(np.int32)
    labels = rs.randint(0, 37, (4, 128)).astype(np.int32)
    losses = {}
    for dp in (4, 1):
        step = parallel.ShardedTrainStep(
            net, optimizer="sgd",
            optimizer_params=dict(learning_rate=0.1),
            example_args=[mx.nd.array(toks[:1])],
            mesh=parallel.make_mesh(devices=jax.devices()[:dp]))
        losses[dp] = [float(step(toks, labels)) for _ in range(3)]
    assert calls and set(calls) == {4}, calls   # dp=1: the plain op
    np.testing.assert_allclose(losses[4], losses[1], rtol=1e-5)


def test_flash_backward_matches_reference_vjp():
    """The tiled backward kernels (dq/dk/dv from lse residuals, no
    L x L tensor) must match autodiff through the XLA oracle."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.flash import (_flash,
                                               _reference_attention)

    rs = np.random.RandomState(0)
    for causal in (True, False):
        for bh, l, d in [(2, 128, 32), (3, 256, 16)]:
            q = jnp.asarray(rs.randn(bh, l, d), jnp.float32)
            k = jnp.asarray(rs.randn(bh, l, d), jnp.float32)
            v = jnp.asarray(rs.randn(bh, l, d), jnp.float32)
            g = jnp.asarray(rs.randn(bh, l, d), jnp.float32)
            scale = 1.0 / np.sqrt(d)
            out, vjp = jax.vjp(
                lambda a, b, c: _flash(a, b, c, causal, scale, True, 0),
                q, k, v)
            ref_out, ref_vjp = jax.vjp(
                lambda a, b, c: _reference_attention(
                    a, b, c, causal, scale), q, k, v)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(ref_out),
                                       rtol=2e-4, atol=2e-4)
            for got, want, name in zip(vjp(g), ref_vjp(g),
                                       ("dq", "dk", "dv")):
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), rtol=2e-3,
                    atol=2e-3, err_msg=f"{name} causal={causal} "
                    f"shape={(bh, l, d)}")


def test_long_sequence_stays_on_pallas_path():
    """The r5 streaming kernels hold VMEM at O(block) regardless of
    sequence length, so long-context shapes stay on the Pallas path
    (the r4 whole-sequence staging fell back past L*D ~ 2^20) and
    must match the reference end to end, gradients included."""
    from incubator_mxnet_tpu.ops import flash as flash_mod

    # L*D here is deliberately above any per-block budget story:
    # 2048*16 tiles into 16x16 blocks of the 128-grid
    q, k, v = _rand(1, 2048, 16)
    assert flash_mod._supported(q, k)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _reference_attention(q, k, v, True, 1.0 / np.sqrt(16))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_f(fq, fk, fv):
        return (flash_attention(fq, fk, fv, causal=True,
                                interpret=True) ** 2).sum()

    def loss_r(fq, fk, fv):
        return (_reference_attention(fq, fk, fv, True,
                                     1.0 / np.sqrt(16)) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_sliding_window_attention():
    """window > 0: sliding-window causal (Mistral-style local
    attention) on the streaming kernels — numerics match the masked
    reference, all three gradients included, and out-of-band blocks
    are skipped (compute O(L * window))."""
    rs = np.random.RandomState(4)
    q, k, v = _rand(2, 512, 16)
    for w in (64, 200):
        out = flash_attention(q, k, v, causal=True, interpret=True,
                              window=w)
        ref = _reference_attention(q, k, v, True,
                                   1.0 / np.sqrt(16), window=w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def loss_f(fq, fk, fv):
        return (flash_attention(fq, fk, fv, causal=True,
                                interpret=True, window=128) ** 2) \
            .sum()

    def loss_r(fq, fk, fv):
        return (_reference_attention(fq, fk, fv, True,
                                     1.0 / np.sqrt(16),
                                     window=128) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)

    # contract errors
    import pytest
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=64)
    with pytest.raises(ValueError, match=">= 0"):
        flash_attention(q, k, v, window=-1)

    # window wider than the sequence == plain causal
    full = flash_attention(q, k, v, causal=True, interpret=True)
    wide = flash_attention(q, k, v, causal=True, interpret=True,
                           window=4096)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(full),
                               rtol=1e-6)


def test_sliding_window_banded_grid_math():
    """The banded inner grid covers only in-window tiles (compute
    AND DMA are O(L * window)): grid-length and index/validity
    bookkeeping checked directly."""
    from incubator_mxnet_tpu.ops.flash import (_band_k_index,
                                               _band_nj,
                                               _band_q_index)
    bq = bk = 128
    nk = 64                      # L = 8192
    # window 256 -> at most (128+256-2)//128 + 2 = 4 k-tiles per
    # q-tile, NOT 64
    nj = _band_nj(256, bq, bk, nk)
    assert nj == 4, nj
    assert _band_nj(256, bq, bk, 2) == 2     # capped at full count

    # q-tile 32 with window 256 sees k positions 3841..4223 ->
    # tiles 30..32
    iqs, valids = [], []
    for j in range(nj):
        jk, valid = _band_k_index(32, j, bq, bk, nk, 256)
        iqs.append(int(jk)), valids.append(bool(valid))
    assert iqs[:3] == [30, 31, 32], iqs
    assert valids[:3] == [True, True, True]
    assert not valids[3]          # clamp duplicate excluded

    # dkv: k-tile 30 is seen by q tiles 30..32 (window 256)
    got = [(int(_band_q_index(30, j, bq, bk, nk, 256)[0]),
            bool(_band_q_index(30, j, bq, bk, nk, 256)[1]))
           for j in range(nj)]
    assert [g for g, ok in got if ok] == [30, 31, 32], got

    # cross-length window rejected
    import pytest
    q = jnp.zeros((1, 256, 16), jnp.float32)
    k = jnp.zeros((1, 128, 16), jnp.float32)
    with pytest.raises(ValueError, match="lq == lk"):
        flash_attention(q, k, k, causal=True, window=64)


# bfloat16 keeps 8 significant bits, so one rounding moves a value by
# at most 2^-9 of itself: the kernel's result and the reference's are
# each rounded once, P and dS once more inside the kernel.  Four such
# steps at the largest reference value.
BF16_TOL = 2.0 ** -7


# the rows ops.flash._tiles gives a length at its most (bfloat16, a
# head of 16, no window): 1024 where it divides, else 512, 256, 128
TILE_OF = {128: 128, 384: 128, 640: 128, 768: 256, 1024: 1024,
           1536: 512, 2048: 1024}


@pytest.mark.parametrize("mode", ["causal", "cross", "window256"])
@pytest.mark.parametrize("l", sorted(TILE_OF))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_every_tile_size_matches_reference(dtype, l, mode):
    """Output and the three gradients at every tile size the tile
    function can choose and every fall-back to a smaller one — a
    larger does not divide the length (TILE_OF), does not fit the
    VMEM budget (float32) or is over twice the window — causal,
    non-causal with lq != lk (key tiles of 256), and the band;
    float32 operands at the float32 tolerances of the tests above,
    bfloat16 operands at bfloat16's 8 bits."""
    from incubator_mxnet_tpu.ops.flash import _tiles
    causal = mode != "cross"
    window = 256 if mode == "window256" else 0
    lk = 256 if mode == "cross" else l
    # float32 blocks of 1024 x 1024 are over the VMEM budget (of
    # 1024 x 256 not), and a tile is no longer than twice the window
    tile = min(TILE_OF[l],
               512 if mode == "window256" or
               (dtype, mode) == ("float32", "causal") else 1024)
    assert _tiles(l, lk, 16, dtype, window) == (
        tile, 256 if mode == "cross" else tile)
    rs = np.random.RandomState(l)
    q, g = (jnp.asarray(rs.normal(0, 1, (1, l, 16)), dtype)
            for _ in range(2))
    k, v = (jnp.asarray(rs.normal(0, 1, (1, lk, 16)), dtype)
            for _ in range(2))
    out, vjp = jax.vjp(
        lambda a, b, c: flash_attention(a, b, c, causal=causal,
                                        interpret=True, window=window),
        q, k, v)
    ref, ref_vjp = jax.vjp(
        lambda a, b, c: _reference_attention(a, b, c, causal, 0.25,
                                             window=window), q, k, v)
    pairs = zip(("out", "dq", "dk", "dv"), (out,) + vjp(g),
                (ref,) + ref_vjp(g))
    for name, got, want in pairs:
        assert got.dtype == want.dtype == jnp.dtype(dtype), name
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if dtype == "float32":
            tol = 2e-5 if name == "out" else 1e-3
            np.testing.assert_allclose(got, want, rtol=tol, atol=tol,
                                       err_msg=name)
        else:
            err = np.abs(got - want).max() / np.abs(want).max()
            assert err <= BF16_TOL, (name, err)


def _walk(index_map, index, n_res, nj):
    """resident tile -> the streamed tiles of its live steps, in
    order; a step that is not live has to name the tile of the step
    before it (no DMA), and the index_map the tile ``index`` gives."""
    live_steps = {}
    for i in range(n_res):
        before = None
        for j in range(nj):
            tile, live = (int(t) for t in index(i, j))
            assert int(index_map(0, i, j)[1]) == tile
            if live:
                live_steps.setdefault(i, []).append(tile)
            else:
                assert tile == before, (i, j, tile, before)
            before = tile
    return live_steps


def test_the_cells_call_in_grid_steps():
    """What needs no chip of the train cell's call, (64, 2048, 64)
    bfloat16 causal: the tiles, the grid's length, and — by walking
    the index maps — that a step past a tile's last live block names
    the block before it (the pipeline fetches nothing for it) and is
    not live, for the plain triangle as
    test_sliding_window_banded_grid_math has it for the band."""
    from incubator_mxnet_tpu.ops.flash import (_band_k_index,
                                               _band_q_index, _k_grid,
                                               _on_edge, _q_grid,
                                               _tiles)
    bh, d = 64, 64
    for l, tiles, steps in ((2048, (1024, 1024), 256),
                            (1536, (512, 512), 576)):
        bq, bk = _tiles(l, l, d, jnp.bfloat16, 0)
        assert (bq, bk) == tiles
        nq, nk = l // bq, l // bk
        nj_k, kmap = _k_grid(True, 0, bq, bk, nk)
        nj_q, qmap = _q_grid(True, 0, bq, bk, nq)
        # the cell: 256 grid steps a kernel where tiles of 128 made
        # 16,384
        assert bh * nq * nj_k == bh * nk * nj_q == steps
        # forward and dq: q-tile i sees k-tiles 0..i, in order
        assert _walk(
            kmap, lambda i, j: _band_k_index(i, j, bq, bk, nk, 0),
            nq, nj_k) == {i: list(range(i + 1)) for i in range(nq)}
        # dk/dv: k-tile jk is seen by q-tiles jk..nq-1
        assert _walk(
            qmap, lambda i, j: _band_q_index(i, j, bq, bk, nq, 0),
            nk, nj_q) == {i: list(range(i, nq)) for i in range(nk)}
        # the mask on the diagonal's blocks alone
        assert [[bool(_on_edge(i, jk, bq, bk, 0))
                 for jk in range(i + 1)] for i in range(nq)] == [
                     [False] * i + [True] for i in range(nq)]
    # the band's lower edge cuts too: window 256 reaches one tile back
    assert bool(_on_edge(2, 1, 512, 512, 256))
    assert not bool(_on_edge(2, 1, 512, 512, 1024))
    # a length that a size does not divide falls to the next one
    assert _tiles(768, 768, d, jnp.bfloat16, 0) == (256, 256)
    assert _tiles(640, 2048, d, jnp.bfloat16, 0) == (128, 1024)
    assert _tiles(64, 64, d, jnp.bfloat16, 0) == (64, 64)
    # operands too wide for the VMEM budget at 1024 rows take fewer
    assert _tiles(2048, 2048, 128, jnp.bfloat16, 0) == (1024, 1024)
    assert _tiles(2048, 2048, d, jnp.float32, 0) == (512, 512)
    assert _tiles(2048, 2048, 512, jnp.float32, 0) == (256, 256)
    # and under a window a tile is no longer than twice the window
    assert _tiles(4096, 4096, d, jnp.bfloat16, 256) == (512, 512)
    assert _tiles(4096, 4096, d, jnp.bfloat16, 64) == (128, 128)
    assert _tiles(4096, 4096, d, jnp.bfloat16, 4096) == (1024, 1024)
