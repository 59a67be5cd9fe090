"""``NemotronHLM`` (Mamba-2 mixers, latent ReLU² experts, grouped-query
attention with no position encoding) against the plain reference, at
a small size on the CPU: the Mamba-2 pieces of ``ops/ssm.py`` against
the recurrence, prefill then decode through ``ServingEngine`` with a
state a slot beside the paged keys and values, and the routed layer's
ungated, latent form and its shares.  Weights are seeded
(benchmark/weights.py, the harness's own path); each tolerance says
why."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import incubator_mxnet_tpu as mx  # noqa: E402
from benchmark import train, weights  # noqa: E402
from benchmark.models import nemotron_h_lm as fam  # noqa: E402
from benchmark.reference import nemotron_h as ref  # noqa: E402
from incubator_mxnet_tpu import telemetry, tracing  # noqa: E402
from incubator_mxnet_tpu.ops import moe, ssm  # noqa: E402
from incubator_mxnet_tpu.serving import ServingEngine  # noqa: E402

# hidden 64; Mamba: 16 heads of 8 in 2 groups of state 16, chunks of
# 8; 8 experts of 32 in a latent of 24, 4 held, top 3; 4 query heads
# and 2 kv heads of 16; every kind of layer
CFG = {"family": "nemotron_h_lm", "hidden_size": 64,
       "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
       "vocab_size": 97, "max_position_embeddings": 4096,
       "mamba_num_heads": 16, "mamba_head_dim": 8, "n_groups": 2,
       "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "n_routed_experts": 4,
       "published": {"n_routed_experts": 8}, "num_experts_per_tok": 3,
       "moe_intermediate_size": 32, "moe_latent_size": 24,
       "moe_shared_expert_intermediate_size": 48,
       "routed_scaling_factor": 5.0, "norm_topk_prob": True,
       "norm_eps": 1e-5, "rope_theta": 10000}
SEED = 2147483693          # over 2**31, as the benchmark's seeds may be

# The program against the reference's full forward: per position the
# widest gap between logits, over the spread of the reference's logits.
# float32 both: the same products in another order (the chunked scan's
# sums against the recurrence's, a token at a time), a few units in the
# last place over five layers.  Held at every position.
TOL = 2e-5


@pytest.fixture(scope="module")
def built():
    """(the program with seeded float32 leaves as the harness sets it
    up, the same leaves for the reference)."""
    shapes = fam.param_shapes(CFG)
    block = train.settled_block(fam, mx, CFG, mx.cpu(), shapes, SEED,
                                trained=False, dtype="float32")
    return block, weights.make(shapes, SEED)


def _tokens(n, length, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG["vocab_size"], (n, length)).astype(np.int32)


def _gap(got, want):
    want = np.asarray(want, np.float64)
    rows = np.abs(np.asarray(got, np.float64) - want).max(-1)
    return float(rows.max() / want.std())


def _recurrence(x, dt, a, b, c, h):
    """``h_t = exp(dt A) h + dt x (x) B``, ``C . h_t``, a row at a time,
    in numpy float64."""
    rep = x.shape[1] // b.shape[1]
    ys = []
    for t in range(x.shape[0]):
        bh, ch = (np.repeat(v[t], rep, 0) for v in (b, c))
        h = np.exp(dt[t] * a)[:, None, None] * h \
            + (dt[t][:, None] * x[t])[..., None] * bh[:, None, :]
        ys.append((h * ch[:, None, :]).sum(-1))
    return np.stack(ys), h


def _plain_step(h, x, dt, a, b, c, d):
    """What ``ssm.state_step`` computes, on (slots, heads, head_dim,
    d_state), in numpy float64."""
    rep = x.shape[1] // b.shape[1]
    bh, ch = (np.repeat(v, rep, axis=1) for v in (b, c))
    h = h * np.exp(dt * a)[..., None, None] \
        + (dt[..., None] * x)[..., None] * bh[:, :, None, :]
    return (h * ch[:, :, None, :]).sum(-1) + d[None, :, None] * x, h


def _scan_inputs(t, heads=8, p=8, groups=2, n=16, seed=0):
    rs = np.random.RandomState(seed)
    return (rs.randn(t, heads, p), np.abs(rs.randn(t, heads)) * 0.5,
            -np.exp(rs.randn(heads) * 0.3), rs.randn(t, groups, n),
            rs.randn(t, groups, n), rs.randn(heads, p, n))


@pytest.mark.parametrize("length,chunk", [(32, 8), (37, 8), (5, 16)])
def test_the_chunked_scan_is_the_recurrence(length, chunk):
    """Whole chunks, a last chunk cut short, a sequence shorter than a
    chunk: outputs and the state after the last row.  float32 against
    float64; the products' sums of a chunk in another order."""
    args = _scan_inputs(length)
    want_y, want_h = _recurrence(*args)
    y, h = ssm.chunked_scan(*(a.astype(np.float32) for a in args), chunk)
    assert np.abs(np.asarray(y) - want_y).max() < 1e-4 * np.abs(
        want_y).max()
    assert np.abs(np.asarray(h) - want_h).max() < 1e-4 * np.abs(
        want_h).max()


def test_padding_rows_leave_the_state_and_the_tail():
    """Rows past ``true_len`` with dt 0 and x 0 (a prefill's padding):
    the state after them is the state after the real rows, and the
    conv's tail is the one its real rows leave."""
    x, dt, a, b, c, h0 = (v.astype(np.float32)
                          for v in _scan_inputs(21, seed=1))
    _, real = ssm.chunked_scan(x[:13], dt[:13], a, b[:13], c[:13], h0, 8)
    x[13:], dt[13:] = 0, 0
    _, padded = ssm.chunked_scan(x, dt, a, b, c, h0, 8)
    np.testing.assert_allclose(padded, real, rtol=1e-6, atol=1e-6)
    rows = np.random.RandomState(2).randn(21, 6).astype(np.float32)
    tail = np.random.RandomState(3).randn(3, 6).astype(np.float32)
    w, bias = np.ones((6, 4), np.float32), np.zeros(6, np.float32)
    _, cut = ssm.causal_conv(rows[:13], tail, w, bias, 13)
    _, after = ssm.causal_conv(rows, tail, w, bias, 13)
    np.testing.assert_array_equal(cut, rows[10:13])
    np.testing.assert_array_equal(after, cut)
    _, short = ssm.causal_conv(rows[:2], tail, w, bias, 2)
    np.testing.assert_array_equal(short, np.concatenate([tail[2:],
                                                         rows[:2]]))


def test_the_state_step_kernel_is_the_plain_update():
    """``ssm_state_step`` interpreted, on the pool's layout, against the
    update on (slots, heads, head_dim, d_state): every slot, an idle one
    (dt 0) left as it was; the state written in place of the one read
    (the call's only output of the pool's shape)."""
    rs = np.random.RandomState(4)
    slots, heads, p, groups, n = 3, 16, 8, 2, 16
    h = rs.randn(slots, heads, p, n).astype(np.float32)
    x = rs.randn(slots, heads, p).astype(np.float32)
    dt = np.abs(rs.randn(slots, heads)).astype(np.float32)
    dt[1] = 0
    a = -np.exp(rs.randn(heads)).astype(np.float32)
    b, c = (rs.randn(slots, groups, n).astype(np.float32)
            for _ in range(2))
    d = rs.randn(heads).astype(np.float32)
    want_y, want_h = _plain_step(h, x, dt, a, b, c, d)
    pool = ssm.to_rows(jnp.asarray(h), groups)
    assert pool.shape == (slots,) + ssm.state_shape(heads, p, groups, n)
    y, new = ssm.state_step(pool, x, dt, a, b, c, d)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ssm.from_rows(new, p), want_h, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(new[1], pool[1])


def test_eager_forward_is_the_references(built):
    block, params = built
    toks = _tokens(2, 29)
    got = block.forward(mx.nd.array(toks, dtype="int32")).asnumpy()
    want = ref.logits(params, toks, CFG, "f32")
    assert got.shape == (2, 29, CFG["vocab_size"])
    assert _gap(got, want) < TOL


@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_a_planted_fault_moves_the_references_logits(built, fault):
    """Each of the reference's planted faults is a different model:
    ten times the float32 tolerance apart and more, at the median
    position past the first few (at these widths the Mamba layers' part
    of the residual is small: the gated norm over all values moves the
    logits least, by some 50 times the tolerance)."""
    _, params = built
    toks = _tokens(1, 24, 9)
    want = np.asarray(ref.logits(params, toks, CFG, "f32"))[0, 4:]
    bad = np.asarray(ref.logits(params, toks, CFG, "f32",
                                fault=fault))[0, 4:]
    rows = np.abs(bad - want).max(-1)
    assert np.median(rows) / want.std() > 10 * TOL


def _served_logits(eng, prompts, new):
    """Each request's logits at every position it decided, by driving
    ``step()`` one at a time (``keep_logits`` keeps the last)."""
    reqs = [eng.submit(p, new) for p in prompts]
    got = {r.id: [] for r in reqs}
    while eng.has_work():
        seen = {r.id: len(r.generated) for r in reqs}
        eng.step()
        for r in reqs:
            if len(r.generated) > seen[r.id]:
                got[r.id].append(np.asarray(r.logits, np.float32)
                                 .reshape(-1))
    return reqs, got


def _engine(block, **kw):
    return ServingEngine(block, **{**dict(
        max_batch=3, block_size=4, num_blocks=80, keep_logits=True,
        max_len=64), **kw})


def test_prefill_then_decode_is_the_references(built):
    """What ``ServingEngine`` serves (the prefill's chunked scan into
    the slot's state, then 9 decode steps of the state-step kernel,
    interpreted, and the paged read of the attention layer) against the
    reference's full forward over prompt and served tokens, logit for
    logit at every position decided: prompts of 30, 13 and 21 tokens
    cross chunks (8) and blocks (4), into buckets 32, 16 and 32."""
    block, params = built
    eng = _engine(block)
    pools = [(c["name"], c.get("per", "token"), len(p), p[0].shape)
             for c, p in zip(eng.cache_spec, eng._pools)]
    assert pools == [("k", "token", 1, (80, 4, 32)),
                     ("v", "token", 1, (80, 4, 32)),
                     ("ssm_state", "slot", 2, (3, 2, 16, 64)),
                     ("conv_tail", "slot", 2, (3, 3, 192))]
    prompts = [t[:n] for t, n in zip(_tokens(3, 30, 1), (30, 13, 21))]
    reqs, got = _served_logits(eng, prompts, 10)
    for r, prompt in zip(reqs, prompts):
        assert r.state == "finished" and len(r.generated) == 10
        seq = np.concatenate([prompt, r.generated])[None]
        want = np.asarray(ref.logits(params, seq, CFG, "f32"))[0]
        at = len(prompt) - 1 + np.arange(10)
        assert _gap(np.stack(got[r.id]), want[at]) < TOL
        assert list(np.argmax(want[at], -1)) == r.generated


def test_a_reused_slot_serves_what_a_fresh_engine_serves(built):
    """One slot: a request, then another in the same slot, whose
    prefill starts the state from zero; its logits are a fresh
    engine's.  Every prefill counts a slot's reset."""
    block, _ = built
    first, second = _tokens(2, 19, 5)
    resets = telemetry.counter("serving_state_slot_resets_total").value
    eng = _engine(block, max_batch=1)
    _served_logits(eng, [first], 7)
    _, reused = _served_logits(eng, [second], 6)
    _, fresh = _served_logits(_engine(block, max_batch=1), [second], 6)
    np.testing.assert_array_equal(np.stack(*reused.values()),
                                  np.stack(*fresh.values()))
    assert telemetry.counter(
        "serving_state_slot_resets_total").value - resets == 3


def test_the_prefix_cache_is_bypassed_and_counted(built):
    """A state a slot is no row of blocks: a prompt whose first blocks
    a finished request left behind is prefilled whole (no cached
    token), and each such admission is counted."""
    block, _ = built
    eng = _engine(block, prefix_cache=True)
    assert not eng.cache.enabled
    base = telemetry.counter("serving_prefix_cache_bypassed_total").value
    hits = telemetry.counter("serving_prefix_cache_hits_total").value
    prompt = _tokens(1, 24, 6)[0]
    tracing.reset_for_tests()
    _served_logits(eng, [prompt], 3)
    _served_logits(eng, [prompt], 3)
    admits = tracing.events("serve_admit")
    assert [e["cached_tokens"] for e in admits] == [0, 0]
    assert telemetry.counter(
        "serving_prefix_cache_bypassed_total").value - base == 2
    assert telemetry.counter("serving_prefix_cache_hits_total").value \
        == hits


def test_the_pools_and_their_bytes(built):
    """Blocks for the one attention layer, a row a slot for the two
    Mamba layers: a token leaves 2 x 32 float32 values, a slot holds
    two states and two tails; the decode span says what the step's
    states move and the read event which pools are kept a slot."""
    block, _ = built
    eng = _engine(block)
    assert eng.token_bytes == 2 * 32 * 4
    assert eng.state_bytes == 3 * 2 * (2 * 16 * 64 + 3 * 192) * 4
    tracing.reset_for_tests()
    _served_logits(eng, [_tokens(1, 9, 7)[0]], 2)
    read, = tracing.events("serve_paged_read")
    assert (read["state_pools"], read["kv_layers"], read["kv_heads"]) \
        == (["ssm_state", "conv_tail"], 1, 2)


def test_a_preempted_request_resumes_to_the_same_tokens(built):
    """Greedy recompute rebuilds the state: a pool so small that the
    second request is preempted and prefilled again from its tokens,
    and both requests end with what each is served alone."""
    block, _ = built
    prompts = [t[:n] for t, n in zip(_tokens(2, 14, 8), (14, 11))]
    alone = []
    for p in prompts:
        eng = _engine(block, max_batch=1, keep_logits=False)
        r = eng.submit(p, 12)
        eng.run()
        alone.append(r.generated)
    eng = _engine(block, max_batch=2, num_blocks=10, keep_logits=False)
    reqs = [eng.submit(p, 12) for p in prompts]
    eng.run()
    assert sum(r.preemptions for r in reqs) >= 1
    assert [r.generated for r in reqs] == alone


def _layer(held, shared=True, seed=0, rows=40):
    """The routed layer of ``CFG``'s widths on random rows: relu2
    experts in a latent, with the shared expert where ``shared``."""
    rs = np.random.RandomState(seed)
    d, lat, w, s, e = 64, 24, 32, 48, 8

    def m(*shape):
        return jnp.asarray(rs.randn(*shape).astype(np.float32) * 0.2)
    x, router, bias = m(rows, d), m(e, d), m(e)
    fc1, fc2 = m(lat, d), m(d, lat)
    up, down = m(e, w, lat), m(e, lat, w)
    share = (None, m(s, d), m(d, s))
    first, count = held
    return moe.routed_ffn_fn(
        x, router, None, up[first:first + count], down[first:first + count],
        3, select_bias=bias, scale=5.0, held=held,
        shared=share if shared else None, activation="relu2",
        latent=(fc1, fc2))[0]


def test_the_four_shares_add_up_to_the_layer():
    """Four ranks of two experts each, the shared expert on the first
    alone: their sum is the layer that holds all eight (fc2_latent is
    linear, so it may go after each rank's part)."""
    whole = _layer((0, 8))
    parts = sum(_layer((2 * i, 2), shared=i == 0) for i in range(4))
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-4)


def test_the_ungated_grouped_product_is_the_tiles():
    """The grouped Pallas product (interpreted) with ReLU² experts in a
    latent of whole lanes gives what the loop over tiles gives."""
    rs = np.random.RandomState(3)
    t, d, lat, w, e, k = 32, 128, 128, 256, 8, 4
    x = jnp.asarray(rs.randn(t, lat).astype(np.float32))
    order = jnp.asarray(rs.permutation(t * k).astype(np.int32))
    pairs = jnp.asarray(np.bincount(rs.randint(0, e, t * k), minlength=e)
                        .astype(np.int32))
    flat = jnp.asarray(rs.rand(t * k).astype(np.float32))
    up = jnp.asarray(rs.randn(e, w, lat).astype(np.float32) * 0.1)
    down = jnp.asarray(rs.randn(e, d, w).astype(np.float32) * 0.1)
    tiles, _ = moe._experts_tiled(x, order, pairs, flat, None, up, down,
                                  k, 0, 16, activation="relu2")
    grouped, _ = moe._experts_grouped(x, order, pairs, flat, None, up,
                                      down, k, 0, interpret=True,
                                      activation="relu2")
    np.testing.assert_allclose(grouped, tiles, rtol=1e-4, atol=1e-3)

