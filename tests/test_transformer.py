"""TransformerLM model family (gluon/model_zoo/transformer.py):
causal attention semantics, convergence through the mesh train step,
and bf16 mixed-precision."""
import numpy as np

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import parallel
from incubator_mxnet_tpu.gluon.model_zoo.transformer import (
    TransformerLM, transformer_lm)


def _tiny(vocab=37, **kw):
    cfg = dict(d_model=32, n_layers=2, n_heads=4, max_len=16)
    cfg.update(kw)
    mx.random.seed(0)
    net = TransformerLM(vocab, **cfg)
    net.initialize(mx.initializer.Xavier())
    return net


def _lm_loss(outputs, labels):
    logits = outputs[0].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(
        jnp.take_along_axis(logp, labels[..., None], axis=-1))


def test_forward_shape_and_determinism():
    net = _tiny()
    toks = mx.nd.array(np.random.RandomState(0)
                       .randint(0, 37, (2, 8)).astype("int32"))
    out = net(toks)
    assert out.shape == (2, 8, 37)
    np.testing.assert_allclose(out.asnumpy(), net(toks).asnumpy())


def test_causality():
    # changing a future token must not change earlier logits
    net = _tiny()
    rs = np.random.RandomState(1)
    a = rs.randint(0, 37, (1, 8)).astype("int32")
    b = a.copy()
    b[0, 5:] = (b[0, 5:] + 7) % 37
    oa = net(mx.nd.array(a)).asnumpy()
    ob = net(mx.nd.array(b)).asnumpy()
    np.testing.assert_allclose(oa[0, :5], ob[0, :5], atol=1e-5)
    assert np.abs(oa[0, 5:] - ob[0, 5:]).max() > 1e-4


def test_trains_on_mesh():
    net = _tiny()
    rs = np.random.RandomState(0)
    toks = jnp.asarray(rs.randint(0, 37, (8, 8)), jnp.int32)
    labels = jnp.asarray(rs.randint(0, 37, (8, 8)), jnp.int32)
    step = parallel.ShardedTrainStep(
        net, optimizer="adam",
        optimizer_params=dict(learning_rate=1e-2), loss_fn=_lm_loss,
        example_args=[mx.nd.array(np.zeros((2, 8), "int32"))])
    losses = [float(step(toks, labels)) for _ in range(25)]
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_bf16_compute_path():
    net = _tiny()
    rs = np.random.RandomState(0)
    toks = jnp.asarray(rs.randint(0, 37, (8, 8)), jnp.int32)
    labels = jnp.asarray(rs.randint(0, 37, (8, 8)), jnp.int32)
    step = parallel.ShardedTrainStep(
        net, optimizer="sgd",
        optimizer_params=dict(learning_rate=0.1), loss_fn=_lm_loss,
        example_args=[mx.nd.array(np.zeros((2, 8), "int32"))],
        compute_dtype=jnp.bfloat16)
    l0 = float(step(toks, labels))
    l1 = float(step(toks, labels))
    assert np.isfinite(l0) and np.isfinite(l1)
    # masters stay fp32
    assert all(v.dtype == jnp.float32 for v in step.params.values())


def test_factory_presets():
    net = transformer_lm(vocab_size=100, size="small", n_layers=1,
                        max_len=8)
    assert net.n_layers == 1 and net._d == 768


def test_max_len_guard():
    net = _tiny(max_len=8)
    import pytest
    with pytest.raises(ValueError, match="max_len"):
        net(mx.nd.array(np.zeros((1, 9), "int32")))


def test_generate_greedy_matches_naive():
    # the scan+KV-cache decoder must agree exactly with re-running
    # the full forward and taking argmax of the last position
    net = _tiny(max_len=16)
    rs = np.random.RandomState(3)
    prompt = rs.randint(0, 37, (2, 4)).astype("int32")
    out = net.generate(mx.nd.array(prompt), max_new_tokens=5)
    assert out.shape == (2, 9)
    got = out.asnumpy()
    np.testing.assert_array_equal(got[:, :4], prompt)

    # the naive side at one shape: the model is causal, so zeros
    # after position n leave the logits at n-1 as they are, and the
    # eager forward compiles once and not once per length
    cur = np.zeros((2, 9), "int32")
    cur[:, :4] = prompt
    for n in range(4, 9):
        logits = net(mx.nd.array(cur)).asnumpy()
        cur[:, n] = logits[:, n - 1].argmax(-1)
    np.testing.assert_array_equal(got, cur)


def test_generate_sampled_and_guard():
    import pytest
    net = _tiny(max_len=16)
    prompt = mx.nd.array(np.zeros((1, 4), "int32"))
    s1 = net.generate(prompt, 4, temperature=1.0,
                      rng=jax.random.PRNGKey(1)).asnumpy()
    s2 = net.generate(prompt, 4, temperature=1.0,
                      rng=jax.random.PRNGKey(1)).asnumpy()
    np.testing.assert_array_equal(s1, s2)   # same key -> same sample
    with pytest.raises(ValueError, match="max_len"):
        net.generate(prompt, 100)


def test_generate_top_k_restricts_support():
    # every sampled continuation token must be in the per-step top-2
    # of the same model's full-forward logits
    net = _tiny(max_len=16)
    prompt = np.random.RandomState(5).randint(0, 37, (1, 4)) \
        .astype("int32")
    out = net.generate(mx.nd.array(prompt), max_new_tokens=5,
                       temperature=1.0, top_k=2,
                       rng=jax.random.PRNGKey(3)).asnumpy()
    cur = np.zeros((1, 9), "int32")      # one shape, as above
    cur[:, :4] = prompt
    for t in range(5):
        logits = net(mx.nd.array(cur)).asnumpy()[:, 3 + t]
        top2 = set(np.argsort(logits[0])[-2:].tolist())
        assert int(out[0, 4 + t]) in top2, (t, out, top2)
        cur[:, 4 + t] = out[:, 4 + t]


def test_generate_top_p_one_keeps_all_and_top_k1_is_greedy():
    net = _tiny(max_len=16)
    prompt = mx.nd.array(np.zeros((1, 4), "int32"))
    greedy = net.generate(prompt, 5).asnumpy()
    k1 = net.generate(prompt, 5, temperature=1.0, top_k=1,
                      rng=jax.random.PRNGKey(0)).asnumpy()
    np.testing.assert_array_equal(k1, greedy)
    # nucleus sampling is deterministic for a fixed key, and valid
    s1 = net.generate(prompt, 5, temperature=1.0, top_p=0.3,
                      rng=jax.random.PRNGKey(0)).asnumpy()
    s2 = net.generate(prompt, 5, temperature=1.0, top_p=0.3,
                      rng=jax.random.PRNGKey(0)).asnumpy()
    np.testing.assert_array_equal(s1, s2)
    assert ((s1 >= 0) & (s1 < 37)).all()


def test_generate_sampling_arg_validation():
    import pytest
    net = _tiny(max_len=16)
    prompt = mx.nd.array(np.zeros((1, 4), "int32"))
    with pytest.raises(ValueError, match="top_k"):
        net.generate(prompt, 2, temperature=1.0, top_k=-1)
    with pytest.raises(ValueError, match="top_p"):
        net.generate(prompt, 2, temperature=1.0, top_p=0.0)
    # greedy ignores the filters and shares one executable
    net._gen_cache = {}
    net.generate(prompt, 2)
    net.generate(prompt, 2, top_k=50, top_p=0.9)
    assert len(net._gen_cache) == 1


def test_gen_cache_is_lru_not_fifo(monkeypatch):
    """Regression: the decode-executable cache must evict the LEAST
    RECENTLY USED signature, not the oldest inserted — an
    alternating pair of hot signatures at capacity used to thrash
    recompiles under FIFO."""
    net = _tiny(max_len=16)
    builds = []
    real_build = net._build_decode

    def counting_build(b, p, max_new, sample, top_k=0, top_p=1.0):
        builds.append((b, p, max_new))
        return real_build(b, p, max_new, sample, top_k=top_k,
                          top_p=top_p)

    monkeypatch.setattr(net, "_build_decode", counting_build)
    monkeypatch.setattr(TransformerLM, "_GEN_CACHE_MAX", 2)
    prompt_a = mx.nd.array(np.zeros((1, 4), "int32"))
    prompt_b = mx.nd.array(np.zeros((1, 5), "int32"))
    prompt_c = mx.nd.array(np.zeros((1, 6), "int32"))
    net.generate(prompt_a, 2)          # build A
    net.generate(prompt_b, 2)          # build B (cache full)
    net.generate(prompt_a, 2)          # hit A -> A becomes MRU
    net.generate(prompt_c, 2)          # build C, evicts B (LRU)
    assert len(builds) == 3
    net.generate(prompt_a, 2)          # FIFO would have evicted A
    assert len(builds) == 3, \
        "hot signature was evicted despite a recent hit (FIFO)"
    # the pair (A, C) now alternates at capacity with no rebuilds
    for _ in range(3):
        net.generate(prompt_a, 2)
        net.generate(prompt_c, 2)
    assert len(builds) == 3


def test_rope_position_scheme():
    """pos='rope': rotary embeddings — trains, decodes consistently
    with the forward pass through the KV cache, and needs no learned
    position table (no pos embedding parameter)."""
    mx.random.seed(0)
    net = TransformerLM(37, d_model=32, n_layers=2, n_heads=4,
                        max_len=64, pos="rope")
    net.initialize(mx.initializer.Xavier())
    assert not any("embedding1" in n or n.endswith("pos_weight")
                   for n in net.collect_params()), \
        list(net.collect_params())[:6]

    toks = mx.nd.array(np.random.RandomState(0)
                       .randint(0, 37, (2, 16)).astype("int32"))
    out = net.generate(toks, max_new_tokens=4)
    nxt = net(toks).asnumpy()[:, -1].argmax(-1)
    assert (out.asnumpy()[:, 16] == nxt).all()

    # trains through the compiled mesh step
    step = parallel.ShardedTrainStep(
        net, optimizer="adam",
        optimizer_params=dict(learning_rate=1e-2),
        loss_fn=_lm_loss,
        example_args=[mx.nd.array(np.zeros((2, 16), "int32"))])
    rs = np.random.RandomState(0)
    t = jnp.asarray(rs.randint(0, 37, (8, 16)), jnp.int32)
    y = jnp.asarray(rs.randint(0, 37, (8, 16)), jnp.int32)
    losses = [float(step(t, y)) for _ in range(12)]
    assert losses[-1] < losses[0] * 0.9, losses

    import pytest
    with pytest.raises(ValueError, match="pos"):
        TransformerLM(37, pos="sinusoidal")
    # odd head dim: loud error at first use, not a reshape crash
    odd = TransformerLM(37, d_model=24, n_heads=8, pos="rope")
    odd.initialize(mx.initializer.Xavier())
    with pytest.raises(ValueError, match="even"):
        odd(mx.nd.array(np.zeros((1, 4), "int32")))


def test_grouped_query_attention():
    """n_kv_heads < n_heads (GQA): the k/v projections and the decode
    cache shrink to kv head groups while attention math matches the
    full decode <-> forward consistency contract; n_kv_heads ==
    n_heads is exactly MHA."""
    mx.random.seed(0)
    net = TransformerLM(64, d_model=32, n_layers=2, n_heads=8,
                        max_len=64, n_kv_heads=2)
    net.initialize(mx.initializer.Xavier())
    # qkv projection rows: d + 2 * kv * dh = 32 + 2*2*4 = 48
    qkv_w = [p for n, p in net.collect_params().items()
             if "dense0_weight" in n][0]
    assert qkv_w.shape[0] == 48, qkv_w.shape

    toks = mx.nd.array(np.random.RandomState(0)
                       .randint(0, 64, (2, 16)).astype("int32"))
    out = net.generate(toks, max_new_tokens=4)
    nxt = net(toks).asnumpy()[:, -1].argmax(-1)
    assert (out.asnumpy()[:, 16] == nxt).all()

    # trains through the compiled step
    step = parallel.ShardedTrainStep(
        net, optimizer="adam",
        optimizer_params=dict(learning_rate=1e-2),
        loss_fn=_lm_loss,
        example_args=[mx.nd.array(np.zeros((2, 16), "int32"))])
    rs = np.random.RandomState(0)
    t = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
    y = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
    losses = [float(step(t, y)) for _ in range(12)]
    assert losses[-1] < losses[0] * 0.9, losses

    import pytest
    for bad in (3, 0, -2):
        with pytest.raises(ValueError, match="multiple"):
            TransformerLM(64, d_model=32, n_heads=8, n_kv_heads=bad)

    # a cached row shrinks with the kv projections: 2 * 4 lanes of 32
    full = TransformerLM(64, d_model=32, n_layers=2, n_heads=8)
    assert [c["shape"] for c in net._paged_cache()] == [(8,), (8,)]
    assert [c["shape"] for c in full._paged_cache()] == [(32,), (32,)]


def test_factory_modern_preset():
    """transformer_lm(size='modern'): rope + grouped-query — the
    configuration current decoder LMs ship with."""
    net = transformer_lm(128, size="modern", max_len=32, n_layers=2)
    net.initialize(mx.initializer.Xavier())
    assert net.n_kv_heads == 4 and net._pos_kind == "rope"
    out = net(mx.nd.array(np.zeros((1, 8), "int32")))
    assert out.shape == (1, 8, 128)
    import pytest
    with pytest.raises(ValueError, match="unknown size"):
        transformer_lm(128, size="modem")   # typo must not silently
    # build a default model


def test_attn_window_model():
    """TransformerLM(attn_window=N): sliding-window attention — the
    flash (banded-kernel) and exact masked paths agree on the same
    weights, and the combination trains."""
    import os

    mx.random.seed(0)
    net = TransformerLM(64, d_model=32, n_layers=2, n_heads=4,
                        max_len=256, attn_window=128, pos="rope")
    net.initialize(mx.initializer.Xavier())
    toks = mx.nd.array(np.random.RandomState(0)
                       .randint(0, 64, (1, 256)).astype("int32"))
    prev = os.environ.get("MXTPU_FLASH")
    try:
        os.environ["MXTPU_FLASH"] = "1"
        out_flash = net(toks).asnumpy()
        os.environ["MXTPU_FLASH"] = "0"
        out_exact = net(toks).asnumpy()
    finally:
        if prev is None:
            os.environ.pop("MXTPU_FLASH", None)
        else:
            os.environ["MXTPU_FLASH"] = prev
    np.testing.assert_allclose(out_flash, out_exact, rtol=2e-4,
                               atol=2e-4)

    step = parallel.ShardedTrainStep(
        net, optimizer="adam",
        optimizer_params=dict(learning_rate=1e-2),
        loss_fn=_lm_loss,
        example_args=[mx.nd.array(np.zeros((1, 256), "int32"))])
    rs = np.random.RandomState(0)
    t = jnp.asarray(rs.randint(0, 64, (8, 256)), jnp.int32)
    y = jnp.asarray(rs.randint(0, 64, (8, 256)), jnp.int32)
    losses = [float(step(t, y)) for _ in range(6)]
    assert losses[-1] < losses[0], losses

    import pytest
    with pytest.raises(ValueError, match="seq_parallel"):
        TransformerLM(64, attn_window=64, seq_parallel=True)
    with pytest.raises(ValueError, match=">= 0"):
        TransformerLM(64, attn_window=-64)

    # decode honors the window even when context exceeds it
    mx.random.seed(1)
    netw = TransformerLM(64, d_model=32, n_layers=2, n_heads=4,
                         max_len=300, attn_window=64, pos="rope")
    netw.initialize(mx.initializer.Xavier())
    toks2 = mx.nd.array(np.random.RandomState(2)
                        .randint(0, 64, (2, 200)).astype("int32"))
    out = netw.generate(toks2, max_new_tokens=4)
    nxt = netw(toks2).asnumpy()[:, -1].argmax(-1)
    assert (out.asnumpy()[:, 200] == nxt).all()

    # the forward honours the band: two layers of window 64 reach
    # 128 positions back, so the first token cannot move the last row
    moved = toks2.asnumpy().copy()
    moved[:, 0] = (moved[:, 0] + 1) % 64
    np.testing.assert_allclose(
        netw(mx.nd.array(moved)).asnumpy()[:, -1],
        netw(toks2).asnumpy()[:, -1], rtol=0, atol=1e-6)
