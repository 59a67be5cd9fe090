"""``LatentMoELM`` (latent attention, routed dropless experts with a
shared one, a leading dense layer) against the plain reference, at a
small size on the CPU: the eager forward, prefill then decode through
``ServingEngine``'s latent pool, the routed layer and its shares, and
the engine's generalised protocol.  Weights are seeded
(benchmark/weights.py, the harness's own path); each tolerance says
why."""
import functools
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import incubator_mxnet_tpu as mx  # noqa: E402
from benchmark import train, weights  # noqa: E402
from benchmark.models import latent_moe_lm as fam  # noqa: E402
from benchmark.reference import latent_moe as ref  # noqa: E402
from incubator_mxnet_tpu import telemetry, tracing  # noqa: E402
from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
    TransformerLM  # noqa: E402
from incubator_mxnet_tpu.ops import moe  # noqa: E402
from incubator_mxnet_tpu.ops import paged_attention as pa  # noqa: E402
from incubator_mxnet_tpu.ops.moe import (  # noqa: E402
    gated_ffn, route_top_k, routed_ffn_fn)
from incubator_mxnet_tpu.serving import ServingEngine  # noqa: E402
from incubator_mxnet_tpu.serving.engine import \
    PAGED_PROTOCOL  # noqa: E402

sys.path.insert(0, os.path.join(REPO, "tests"))
from _serving import serve, slow_decode  # noqa: E402

# hidden 64, 4 heads, latent 16 + rope 8, 8 experts top 2, one dense
# and two expert layers
CFG = {"family": "latent_moe_lm", "hidden_size": 64,
       "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "intermediate_size": 96, "moe_intermediate_size": 32,
       "n_routed_experts": 8, "n_shared_experts": 1,
       "num_experts_per_tok": 2, "first_k_dense_replace": 1,
       "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
       "rope_theta": 10000, "rope_scaling": None,
       "rope_interleave": True, "scoring_func": "sigmoid",
       "norm_topk_prob": True, "max_position_embeddings": 4096,
       "vocab_size": 97, "num_hidden_layers": 3}
SEED = 2147483659          # over 2**31, as the driver's seeds are

# The program against the reference's full forward: per position the
# widest gap between logits, over the spread of the reference's logits.
#   float32: the same products in another order; a few units in the
#     last place over three layers.  Held at every position.
#   bfloat16: the program carries bfloat16 between operations (8 bits
#     of mantissa); three layers leave about a hundredth of the
#     spread.  Held at the median position: where a token's 2nd and
#     3rd scores lie within a rounding of each other it goes to
#     another expert (the layer is not continuous there), which a
#     position in some dozens does.  The reference in fp8 (4 bits) on
#     the same leaves has to fail the same tolerance twice over.
TOL = {"float32": 2e-5, "bfloat16": 0.04}
OVER = {"float32": np.max, "bfloat16": np.median}


def _built(dtype, cfg=CFG):
    """(the program with seeded leaves as the harness sets it up, the
    same leaves for the reference)."""
    shapes = fam.param_shapes(cfg)
    block = train.settled_block(fam, mx, cfg, mx.cpu(), shapes, SEED,
                                trained=False, dtype=dtype)
    return block, weights.make(shapes, SEED, dtype)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def built(request):
    return (request.param,) + _built(request.param)


@pytest.fixture(scope="module")
def f32():
    return _built("float32")


def _tokens(n, length, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG["vocab_size"], (n, length)).astype(np.int32)


def _gap(got, want, over=np.max):
    want = np.asarray(want, np.float64)
    rows = np.abs(np.asarray(got, np.float64) - want).max(-1)
    return float(over(rows) / want.std())


def _served_logits(eng, prompts, new):
    """Each request's logits at every position it decided, by driving
    ``step()`` one at a time (``keep_logits`` keeps the last; a step
    lands at most one token a request: the admission's from its
    prefill, each later one from the decode step before)."""
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


def test_eager_forward_is_the_references(built):
    dtype, block, params = built
    toks = _tokens(2, 40)
    got = block.forward(mx.nd.array(toks, dtype="int32")).asnumpy()
    want = ref.logits(params, toks, CFG, "f32")
    assert got.shape == (2, 40, CFG["vocab_size"])
    assert _gap(got, want, OVER[dtype]) < TOL[dtype]
    if dtype == "bfloat16":
        low = ref.logits(params, toks, CFG, "fp8")
        assert _gap(low, want, np.median) > 2 * TOL[dtype]


@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_a_planted_fault_moves_the_references_logits(f32, fault):
    """Each of the reference's planted faults is a different model:
    far more than the float32 tolerance apart, at every position
    past the first few (a fault of the routing cannot show before
    the first expert layer has been passed)."""
    _, params = f32
    toks = _tokens(1, 40, 9)
    want = np.asarray(ref.logits(params, toks, CFG, "f32"))
    bad = np.asarray(ref.logits(params, toks, CFG, "f32", fault=fault))
    assert _gap(bad[0, 4:], want[0, 4:], np.median) > 100 * TOL["float32"]


def test_prefill_then_decode_through_the_latent_pool(built):
    """What ``ServingEngine`` serves (expanded prefill into the paged
    latent pool, absorbed decode out of it) against the reference's
    full forward over prompt and served tokens.  Blocks of 4 and a
    floor of one block a bucket: the prompts of 50, 23 and 37 tokens
    cross block boundaries, fall into the buckets 64, 32 and 64, and
    decoding carries the first over 52 (a block) and none over a
    bucket, which only a prompt crosses."""
    dtype, block, params = built
    eng = ServingEngine(block, max_batch=3, block_size=4,
                        num_blocks=80, keep_logits=True, max_len=96)
    assert [(a.shape[1:], str(a.dtype)) for pool in eng._pools
            for a in pool] == [((4, 128), dtype)] * 3   # whole lanes
    assert eng.cache_spec[0]["values"] == 24            # 16 + 8
    prompts = [t[:n] for t, n in zip(_tokens(3, 50, 1), (50, 23, 37))]
    before = len(tracing.events("serve_paged_read"))
    reqs, got = _served_logits(eng, prompts, 6)
    # the engine says which read of the pool it built, and on what
    assert [(e["read"], e["platform"], e["kv_heads"], e["head_dim"],
             e["dtype"]) for e in tracing.events("serve_paged_read")[
                 before:]] == [("plain", "cpu", 1, 128, dtype)]
    # the parent commit's tokens (5e547c0, whose decode step gathered
    # every slot's table row from the pool it had just written), on
    # the CPU, at both dtypes
    assert [r.generated for r in reqs] == [
        [67, 41, 79, 89, 28, 75], [41, 0, 92, 30, 24, 63],
        [29, 31, 15, 92, 30, 53]]
    for r, prompt in zip(reqs, prompts):
        assert r.state == "finished" and len(r.generated) == 6
        seq = np.concatenate([prompt, r.generated])[None]
        want = np.asarray(ref.logits(params, seq, CFG, "f32"))[0]
        at = len(prompt) - 1 + np.arange(6)
        served = np.stack(got[r.id][-5:])
        assert _gap(served, want[at[1:]], OVER[dtype]) < TOL[dtype]
        if dtype == "float32":
            assert list(np.argmax(want[at], -1)) == r.generated
        else:
            low = np.asarray(ref.logits(params, seq, CFG, "fp8"))[0]
            assert _gap(low[at[1:]], want[at[1:]],
                        np.median) > 2 * TOL[dtype]


def test_prefill_logits_are_the_references(built):
    """The first token's logits, from the prefill program alone, of
    seven prompts (a single position may be one whose experts a
    rounding turns: the tolerance is held at the median one)."""
    dtype, block, params = built
    eng = ServingEngine(block, max_batch=1, block_size=4,
                        num_blocks=40, keep_logits=True, max_len=64)
    rows = []
    for n, prompt in zip((41, 7, 33, 18, 25, 12, 37), _tokens(7, 41, 2)):
        req = eng.submit(prompt[:n], 1)
        eng.run()
        want = np.asarray(ref.logits(params, prompt[None, :n], CFG,
                                     "f32"))[0, -1]
        rows.append((np.asarray(req.logits, np.float32).reshape(-1),
                     want))
    got, want = (np.stack(v) for v in zip(*rows))
    assert _gap(got, want, OVER[dtype]) < TOL[dtype]


def test_absorbed_decode_is_expanded_attention(f32):
    """The same program both ways: a token's logits from the decode
    step (attention absorbed into the latent, out of the pool) and
    from the eager forward (keys and values expanded per head).
    float32, so only the order of the sums differs."""
    block, _ = f32
    eng = ServingEngine(block, max_batch=2, block_size=4,
                        num_blocks=40, keep_logits=True, max_len=64)
    prompts = [t[:n] for t, n in zip(_tokens(2, 30, 3), (30, 19))]
    reqs, got = _served_logits(eng, prompts, 5)
    for r, prompt in zip(reqs, prompts):
        seq = np.concatenate([prompt, r.generated])[None]
        full = block.forward(mx.nd.array(seq, dtype="int32")).asnumpy()
        at = len(prompt) + np.arange(4)
        assert _gap(np.stack(got[r.id][-4:]), full[0, at]) < 2e-5


def _chained_against_the_forward(block):
    """Serve three prompts through the chained decode loop, the later
    two admitted while a decode step is in flight, with budgets that
    end at different steps, and hold every served token to the eager
    forward's greedy choice over the prompt and the tokens before it.
    Prompts and budgets add up to one length and one prefill bucket:
    one forward and one prefill program to compile."""
    prompts = [t[:n] for t, n in zip(_tokens(3, 13, 5), (13, 11, 9))]
    budgets = (5, 7, 9)
    eng = ServingEngine(block, max_batch=3, block_size=4, num_blocks=80,
                        max_len=64)
    reqs = [eng.submit(prompts[0], budgets[0])]
    for p, n in zip(prompts[1:], budgets[1:]):
        eng.step()
        assert eng._flight is not None      # admitted mid-flight
        reqs.append(eng.submit(p, n))
    eng.run()
    assert telemetry.counter("serving_decode_chained_total").value > 0
    assert all(r.state == "finished" for r in reqs)
    seqs = np.stack([np.concatenate([p, r.generated])
                     for r, p in zip(reqs, prompts)])
    full = block.forward(mx.nd.array(seqs, dtype="int32")).asnumpy()
    for row, r, p in zip(full, reqs, prompts):
        assert list(np.argmax(row[len(p) - 1:-1], -1)) == r.generated


def test_the_chained_loop_serves_the_forwards_tokens(f32):
    """Decode steps whose tokens come from the step before on the
    device, admissions while a step is in flight, budgets that end in
    flight: float32, every served token is the eager forward's."""
    _chained_against_the_forward(f32[0])


def _counter(name):
    return telemetry.counter(name).value


def test_the_latent_loop_launches_onto_the_step_still_running(
        f32, monkeypatch, profiler_session, newest_spans):
    """A decode step of the latent family is launched while the step
    before still runs (``serving_decode_queued_total``, the launch's
    ``queued``); the greedy tokens are those of a loop that lands
    every step before the next launch, and the eager forward's."""
    block = slow_decode(monkeypatch, f32[0])
    prompts = [t[:n] for t, n in zip(_tokens(3, 13, 11), (13, 11, 9))]
    budgets = (5, 7, 9)
    served = {}
    for land_each in (True, False):
        eng = ServingEngine(block, max_batch=2, block_size=4,
                            num_blocks=80, max_len=64)
        serve(eng, prompts[:1], [2], land_each)    # compiled
        queued0 = _counter("serving_decode_queued_total")
        with profiler_session():
            reqs = serve(eng, prompts, budgets, land_each)
        queued = _counter("serving_decode_queued_total") - queued0
        launches = [e for e in newest_spans()
                    if e["name"] == "serve_decode_dispatch"]
        assert [e["queued"] for e in launches].count(True) == queued
        assert (queued == 0) if land_each else (queued > 0)
        served[land_each] = [r.generated for r in reqs]
    assert served[True] == served[False]
    seqs = [np.concatenate([p, g]) for p, g in zip(prompts, served[True])]
    for p, seq in zip(prompts, seqs):
        full = block.forward(mx.nd.array(seq[None], dtype="int32"))
        assert list(np.argmax(full.asnumpy()[0, len(p) - 1:-1], -1)) \
            == list(seq[len(p):])


def test_a_preemption_under_a_running_step_keeps_the_victims_token(
        f32, monkeypatch):
    """Launches going onto steps still running, and a pool too small
    for both requests: the growth that preempts lands the step in
    flight first, so the victim keeps the token that step computed;
    what each request serves is what it serves alone."""
    block = slow_decode(monkeypatch, f32[0])
    prompts = [t[:n] for t, n in zip(_tokens(2, 30, 7), (30, 26))]
    want = []
    for p in prompts:
        eng = ServingEngine(block, max_batch=2, block_size=4,
                            num_blocks=40, max_len=64)
        req = eng.submit(p, 12)
        eng.run()
        want.append(req.generated)
    eng = ServingEngine(block, max_batch=2, block_size=4,
                        num_blocks=19, max_len=64, prefix_cache=False)
    seen, preempt = [], eng._preempt

    def watched(req):
        seen.append((eng._flight, req.n_past
                     == len(req.prompt) + len(req.generated) - 1))
        preempt(req)
    eng._preempt = watched
    queued0 = _counter("serving_decode_queued_total")
    reqs = [eng.submit(p, 12) for p in prompts]
    eng.run()
    assert seen and all(f is None and kept for f, kept in seen)
    assert _counter("serving_decode_queued_total") > queued0
    assert [r.generated for r in reqs] == want


def test_a_long_context_is_read_in_passes_of_keys(f32):
    """The expanded path walks the context in passes of 512 rows and
    stops where the last query's sight ends: a sequence past one pass
    (600) agrees with the reference as a short one does."""
    block, params = f32
    toks = _tokens(1, 600, 12)
    got = block.forward(mx.nd.array(toks, dtype="int32")).asnumpy()
    want = ref.logits(params, toks, CFG, "f32")
    assert _gap(got, want) < TOL["float32"]


def _layer_leaves(n=8, d=64, width=32, seed=6):
    rs = np.random.RandomState(seed)
    lp = {"router": rs.randn(n, d) * 0.2,
          "router_bias": rs.randn(n) * 0.3,
          "experts_gate": rs.randn(n, width, d) * 0.1,
          "experts_up": rs.randn(n, width, d) * 0.1,
          "experts_down": rs.randn(n, d, width) * 0.1,
          "shared_gate": rs.randn(width, d) * 0.1,
          "shared_up": rs.randn(width, d) * 0.1,
          "shared_down": rs.randn(d, width) * 0.1}
    return {k: jnp.asarray(v, jnp.float32) for k, v in lp.items()}, \
        jnp.asarray(rs.randn(40, d), jnp.float32)


def _routed(h, lp, shared=True, **kw):
    return routed_ffn_fn(
        h, lp["router"], lp["experts_gate"], lp["experts_up"],
        lp["experts_down"], 2, select_bias=lp["router_bias"],
        scale=2.5, shared=(lp["shared_gate"], lp["shared_up"],
                           lp["shared_down"]) if shared else None,
        **kw)


@pytest.mark.parametrize("scoring,bias,normalize", [
    ("sigmoid", True, True), ("sigmoid", False, True),
    ("sigmoid", True, False), ("softmax", False, False),
    ("softmax", True, True)])
def test_the_routed_layer_is_a_loop_over_tokens(scoring, bias,
                                                normalize):
    """The grouped product against the layer written as a loop: each
    token through its chosen experts one by one.  float32; the sums
    run over the same terms in another order."""
    lp, h = _layer_leaves()
    b = lp["router_bias"] if bias else None
    y, stats = routed_ffn_fn(
        h, lp["router"], lp["experts_gate"], lp["experts_up"],
        lp["experts_down"], 2, scoring=scoring, select_bias=b,
        normalize=normalize, scale=2.5)
    choice, weight = route_top_k(h, lp["router"], 2, scoring, b,
                                 normalize, 2.5)
    want = np.zeros(h.shape, np.float32)
    for t in range(h.shape[0]):
        for e, w in zip(np.asarray(choice[t]), np.asarray(weight[t])):
            want[t] += w * np.asarray(gated_ffn(
                h[t:t + 1], lp["experts_gate"][e], lp["experts_up"][e],
                lp["experts_down"][e]))[0]
    np.testing.assert_allclose(y, want, atol=2e-5)
    if normalize:
        np.testing.assert_allclose(np.asarray(weight).sum(-1), 2.5,
                                   rtol=1e-5)
    rows, padded, touched = (int(stats[k]) for k in (
        "routed_rows", "padded_rows", "experts_touched"))
    assert rows - padded == 40 * 2 and rows % 16 == 0
    assert touched == len(np.unique(choice))


@pytest.mark.parametrize("held", [None, (0, 4), (4, 4), (2, 3)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_grouped_product_gives_what_the_tiles_give(dtype, held):
    """The path a TPU takes (the sorted rows through the grouped
    Pallas product, here interpreted) against the loop over tiles
    that every other platform takes, at widths of whole lanes: the
    same products with the same float32 sums, a token's two terms
    added in another order: a unit or two in float32's last place.
    Rows 50.. are padding and 28 or more pairs are not held: what
    lies behind the last held pair is never computed and never
    read."""
    rs = np.random.RandomState(9)
    t, d, width, n, k = 64, 128, 128, 8, 2
    x = jnp.asarray(rs.randn(t, d), dtype)
    router = jnp.asarray(rs.randn(n, d) * 0.2, dtype)
    gate, up = (jnp.asarray(rs.randn(n, width, d) * 0.1, dtype)
                for _ in range(2))
    down = jnp.asarray(rs.randn(n, d, width) * 0.1, dtype)
    first, count = held or (0, n)
    choice, weight = route_top_k(x, router, k)
    local = choice - first
    here = (local >= 0) & (local < count) \
        & (jnp.arange(t) < 50)[:, None]
    key = jnp.where(here, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    pairs = jnp.sum(key[:, None] == jnp.arange(count), axis=0,
                    dtype=jnp.int32)
    args = (x, order, pairs, weight.reshape(-1), gate, up, down, k,
            first)
    tiled, tiled_rows = moe._experts_tiled(*args, 16)
    grouped, rows = moe._experts_grouped(*args, interpret=True)
    assert float(jnp.abs(tiled).max()) > 1.0
    np.testing.assert_allclose(grouped, tiled, atol=2e-6)
    # one tile of 128 rows, gone through once for each expert in it
    assert int(rows) == 128 * int((pairs > 0).sum())
    assert int(tiled_rows) % 16 == 0 \
        and int(tiled_rows) >= int(pairs.sum())


def test_the_router_is_the_references():
    """The same gates as the reference's: sigmoid scores, the bias in
    the choice alone, the chosen scores over their sum times 2.5.
    The bias is large against float32 rounding, so both choose the
    same experts."""
    lp, h = _layer_leaves()
    choice, weight = route_top_k(h, lp["router"], 2,
                                 select_bias=lp["router_bias"],
                                 scale=2.5)
    gates = np.zeros((40, 8), np.float32)
    np.put_along_axis(gates, np.asarray(choice), np.asarray(weight), -1)
    np.testing.assert_allclose(
        gates, ref.route(h, lp["router"], lp["router_bias"], CFG,
                         "f32"), atol=1e-6)
    s = np.asarray(jax.nn.sigmoid(h @ lp["router"].T))
    # the bias moved some choice, and left every weight a plain score
    assert (np.sort(np.argsort(-s, -1)[:, :2], -1)
            != np.sort(choice, -1)).any()


def test_the_shares_add_up():
    """``held=(0, 4)`` and ``held=(4, 4)``, the shared expert given to
    the first alone: the two parts are the whole layer, which is the
    reference's; each part is the reference's part; the stacked
    weights may hold all experts or the held ones."""
    lp, h = _layer_leaves()
    whole, _ = _routed(h, lp)
    np.testing.assert_allclose(
        whole, ref.routed_layer(h, lp, CFG, "f32"), atol=5e-5)
    low, s0 = _routed(h, lp, held=(0, 4))
    high, s1 = _routed(h, lp, shared=False, held=(4, 4))
    np.testing.assert_allclose(low + high, whole, atol=5e-5)
    np.testing.assert_allclose(
        high, ref.routed_layer(h, lp, CFG, "f32", held=(4, 4)),
        atol=5e-5)
    mine = {**lp, **{k: lp[k][4:] for k in ref.EXPERT_LEAVES}}
    only, _ = _routed(h, mine, shared=False, held=(4, 4))
    np.testing.assert_array_equal(only, high)
    real = [int(s["routed_rows"] - s["padded_rows"]) for s in (s0, s1)]
    assert sum(real) == 40 * 2          # every pair, on one share
    with pytest.raises(ValueError):
        _routed(h, lp, held=(6, 4))


def test_padding_rows_are_routed_nowhere():
    lp, h = _layer_leaves()
    valid = jnp.arange(40) < 10
    y, stats = _routed(h, lp, shared=False, valid=valid)
    assert int(stats["routed_rows"] - stats["padded_rows"]) == 20
    assert not np.asarray(y[10:]).any()
    full, _ = _routed(h, lp, shared=False)
    np.testing.assert_allclose(y[:10], full[:10], atol=1e-6)


@pytest.mark.parametrize("mates", [1, 7])
def test_a_request_is_served_alike_alone_and_among_batch_mates(
        f32, mates):
    """Dropless: a token's experts do not turn on who else is in the
    batch.  float32, the same rows in another batch: a row's sums run
    over the same terms, so the logits agree to a few bits and the
    tokens are the same."""
    block, _ = f32
    prompts = _tokens(8, 33, 5)

    def serve(which):
        eng = ServingEngine(block, max_batch=8, block_size=4,
                            num_blocks=120, keep_logits=True,
                            max_len=64)
        reqs, got = _served_logits(eng, [prompts[i] for i in which], 5)
        return reqs[0].generated, np.stack(got[reqs[0].id][-4:])

    alone_toks, alone = serve([0])
    among_toks, among = serve(range(1 + mates))
    assert alone_toks == among_toks
    assert _gap(among, alone) < 1e-5


def test_a_preempted_request_resumes_to_the_same_tokens(f32):
    """A pool too small for both requests' whole length: the later
    one is preempted, re-queued and prefilled again over prompt and
    generated tokens; what it serves is what it serves alone."""
    block, _ = f32
    prompts = [t[:n] for t, n in zip(_tokens(2, 30, 7), (30, 26))]
    want = []
    for p in prompts:
        eng = ServingEngine(block, max_batch=2, block_size=4,
                            num_blocks=40, max_len=64)
        req = eng.submit(p, 12)
        eng.run()
        want.append(req.generated)
    before = telemetry.counter("serving_preemptions_total").value
    eng = ServingEngine(block, max_batch=2, block_size=4,
                        num_blocks=19, max_len=64, prefix_cache=False)
    reqs = [eng.submit(p, 12) for p in prompts]
    eng.run()
    assert telemetry.counter("serving_preemptions_total").value \
        > before
    assert sum(r.preemptions for r in reqs) >= 1
    assert [r.generated for r in reqs] == want


def test_a_prefix_hit_reads_latent_blocks(f32):
    """Two prompts that share their first 24 tokens (6 blocks of 4):
    the second finds them in the prefix cache, prefills its own 9 in
    the 16 bucket, and serves what it serves with the cache off."""
    block, _ = f32
    first = _tokens(1, 40, 8)[0]
    second = np.concatenate([first[:24], _tokens(1, 9, 9)[0]])
    off = ServingEngine(block, max_batch=2, block_size=4,
                        num_blocks=60, max_len=64, prefix_cache=False)
    plain = off.submit(second, 6)
    off.run()
    hits = telemetry.counter("serving_prefix_cache_hits_total").value
    eng = ServingEngine(block, max_batch=2, block_size=4,
                        num_blocks=60, max_len=64, prefix_cache=True)
    eng.submit(first, 2)
    eng.run()
    req = eng.submit(second, 6)
    eng.run()
    assert telemetry.counter(
        "serving_prefix_cache_hits_total").value - hits == 24
    assert req.generated == plain.generated


def _eight_heads(dtype, seed=14):
    """The model at 8 heads (heads in whole sublanes, so a decode step
    lowered for a TPU reads through the paged kernel) and leaves of
    its parameters' shapes drawn as the harness draws them (matrices
    0.02 a standard normal, gains 1 + 0.1 of one): nothing is
    initialized."""
    from incubator_mxnet_tpu.gluon.model_zoo.latent_moe import \
        LatentMoELM
    lm = LatentMoELM(dict(CFG, num_attention_heads=8))
    rs = np.random.RandomState(seed)

    def leaf(name, param):
        base = 1.0 if "norm" in name else 0.0
        spread = 0.1 if "norm" in name else 0.02
        return jnp.asarray(base + spread * rs.randn(*param.shape), dtype)

    wts = {"embed": leaf("embed", lm.embed_weight),
           "norm": leaf("norm", lm.norm),
           "head": leaf("head", lm.head_weight),
           "layers": [{k: leaf(k, p) for k, p in lw.items()}
                      for lw in lm.layers]}
    return lm, wts


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_decode_step_through_the_kernel_is_the_gathers(dtype,
                                                           monkeypatch):
    """The absorbed decode step with the paged kernel (interpreted) in
    the place of the gather the CPU takes, over a pool of random rows
    and six slots (idle, one row, a block's last and first row, past
    two blocks, the whole table row): the first layer's rows are
    written alike to the bit, the logits agree to the bfloat16
    tolerance.  Not to float32's: the kernel's products take bfloat16
    operands at any pool dtype (what a TPU's default precision makes
    of a float32 product), which moves a float32 step by about 2e-3
    of the logits' spread."""
    lm, wts = _eight_heads(dtype)
    bs, mb = 16, 6
    assert lm._paged_read(bs, "tpu")["read"] == "kernel"
    assert lm._paged_read(bs, "cpu")["read"] == "plain"
    rs = np.random.RandomState(15)
    n_past = np.array([0, 1, bs - 1, bs, 2 * bs + 3, mb * bs - 1],
                      np.int32)
    ids = rs.permutation(np.arange(1, 64))
    tables = np.zeros((len(n_past), mb), np.int32)
    at = 0
    for i, n in enumerate(n_past):
        tables[i, :n // bs + 1] = ids[at:at + n // bs + 1]
        at += n // bs + 1
    pools = [jnp.asarray(rs.randn(64, bs, 128), dtype)
             for _ in lm.layers]
    args = (wts, pools, jnp.asarray(tables), jnp.asarray(n_past),
            jnp.asarray(rs.randint(0, CFG["vocab_size"], len(n_past)),
                        jnp.int32))

    def run():
        step = lm._build_paged_step(len(n_past), mb, bs)
        return jax.jit(step)(*args)

    pools_p, _, logits_p = run()
    monkeypatch.setattr(pa, "decode_attention", functools.partial(
        pa.kernel_read, interpret=True))
    pools_k, _, logits_k = run()
    live = n_past > 0
    got, want = np.asarray(logits_k)[live], np.asarray(logits_p)[live]
    print(dtype, _gap(got, want), _gap(got, want, np.median))
    assert 0 < _gap(got, want) and \
        _gap(got, want, OVER["bfloat16"]) < TOL["bfloat16"]
    np.testing.assert_array_equal(pools_k[0], pools_p[0])
    for a, b in zip(pools_k[1:], pools_p[1:]):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=0.1)


def test_max_len_bounds_what_the_decode_program_gathers(f32):
    """``max_len`` bounds a table row, and so what the decode step
    reads: in blocks of 4 (no kernel tiles them) the step gathers a
    slot's 48 positions and nothing of the model's own 4096; in blocks
    of 8 lowered for a TPU it holds no slot's context at all, only the
    table row of 6 blocks that the kernel walks."""
    block, _ = f32
    eng = ServingEngine(block, max_batch=2, block_size=4,
                        num_blocks=64, max_len=48)
    assert eng.max_blocks == 12 and block._max_len == 4096
    step = block._build_paged_step(2, eng.max_blocks, 4)
    tables = jnp.zeros((2, eng.max_blocks), jnp.int32)
    text = str(jax.make_jaxpr(step)(
        eng._wts, eng._pools[0], tables, jnp.ones(2, jnp.int32),
        jnp.zeros(2, jnp.int32)))
    assert "2,48,1,128]" in text.replace(" ", "")  # a slot's context
    assert "4096" not in text
    wide, wts = _eight_heads("float32")
    assert ServingEngine(block, max_batch=2, block_size=8, num_blocks=64,
                         max_len=48).max_blocks == 6
    tpu = jax.jit(wide._build_paged_step(2, 6, 8)).trace(
        wts, [jnp.zeros((64, 8, 128))] * len(wide.layers),
        jnp.zeros((2, 6), jnp.int32), jnp.ones(2, jnp.int32),
        jnp.zeros(2, jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "paged_decode_attention" in tpu and "tensor<2x6xi32>" in tpu
    assert not re.search(r"tensor<2x(48|6x8)x", tpu)
    assert "4096" not in tpu
    with pytest.raises(mx.serving.RequestTooLargeError):
        eng.submit(list(range(40)), 9)
    assert eng.submit(list(range(40)), 8).id == 0
    with pytest.raises(ValueError):
        ServingEngine(block, max_len=5000)


EIGHT = ("_max_len", "n_layers", "_check_paged", "_paged_cache",
         "_decode_weights", "_build_paged_prefill",
         "_build_paged_step", "_decode_workspace_bytes")


class _Offers:
    """The named members of a model, and nothing else of it."""

    def __init__(self, model, names):
        for name in names:
            setattr(self, name, getattr(model, name))


@pytest.fixture(scope="module")
def families(f32):
    mx.random.seed(0)
    opt = TransformerLM(CFG["vocab_size"], d_model=32, n_layers=2,
                        n_heads=4, max_len=64)
    opt.initialize(mx.initializer.Xavier())
    opt(mx.nd.array(np.zeros((1, 4), "int32")))   # deferred shapes
    return {"TransformerLM": opt, "LatentMoELM": f32[0]}


@pytest.mark.parametrize("lacking", EIGHT + (None,))
@pytest.mark.parametrize("family", ["TransformerLM", "LatentMoELM"])
def test_the_engine_serves_by_the_eight_members(families, family,
                                                lacking):
    """Each of the eight is asked for by name, and the eight are all
    a model has to bring: no count of its operations, no class."""
    assert PAGED_PROTOCOL == EIGHT
    model = families[family]
    kw = dict(max_batch=2, block_size=4, num_blocks=32)
    if lacking is not None:
        less = _Offers(model, [n for n in EIGHT if n != lacking])
        with pytest.raises(TypeError,
                           match=rf"_Offers lacks {lacking}$"):
            ServingEngine(less, **kw)
        return
    prompt = [int(t) for t in _tokens(1, 7, seed=3)[0]]
    served = []
    for offered in (_Offers(model, EIGHT), model):
        eng = ServingEngine(offered, **kw)
        req = eng.submit(prompt, 5)
        eng.run()
        assert req.state == "finished" and len(req.generated) == 5
        served.append(req.generated)
    assert served[0] == served[1]


def test_the_engine_asks_for_the_protocol_not_the_class(f32):
    with pytest.raises(TypeError, match="paged protocol"):
        ServingEngine(mx.gluon.nn.Dense(4))
    block, _ = f32
    with pytest.raises(ValueError, match="int8"):
        ServingEngine(block, quantize="int8")
    eng = ServingEngine(block, max_batch=2, block_size=4,
                        num_blocks=16)
    assert eng.stats()["cache"] == {
        "pools": [{"name": "latent", "shape": (128,),
                   "dtype": "float32", "values": 24}],
        "bytes_per_token": 3 * 128 * 4}
    # no isinstance on a model class is left in serving/
    serving = os.path.join(REPO, "incubator_mxnet_tpu", "serving")
    for name in os.listdir(serving):
        if name.endswith(".py"):
            with open(os.path.join(serving, name)) as f:
                assert "TransformerLM)" not in f.read(), name


def test_a_snapshot_of_the_new_model_restores(f32):
    block, _ = f32
    eng = ServingEngine(block, max_batch=2, block_size=4,
                        num_blocks=40, max_len=64)
    reqs = [eng.submit(p, 8) for p in _tokens(2, 20, 10)]
    for _ in range(3):
        eng.step()
    snap = eng.snapshot()
    assert snap["engine"]["max_len"] == 64
    assert snap["engine"]["cache"][0]["name"] == "latent"
    eng.run()
    again = ServingEngine.restore(block, snap)
    assert again.max_len == 64 and again.max_blocks == 16
    done = again.run()          # id -> prompt and served tokens
    assert [done[r.id] for r in reqs] == [r.tokens for r in reqs]


def test_moe_counters_come_back_with_the_tokens(f32):
    names = ("routed_rows", "padded_rows", "experts_touched",
             "layer_steps")

    def read():
        return [telemetry.counter(f"serving_moe_{n}_total").value
                for n in names]
    block, _ = f32
    before = read()
    eng = ServingEngine(block, max_batch=2, block_size=4,
                        num_blocks=64, max_len=64)
    eng.submit(_tokens(1, 20, 8)[0], 3)
    eng.run()
    rows, padded, touched, steps = (
        a - b for a, b in zip(read(), before))
    # 20 prompt rows and 2 decoded tokens through 2 routed layers, 2
    # choices each; a decode step's slot reads 2 experts a layer
    assert rows - padded == 22 * 2 * 2 and rows % 16 == 0
    assert steps == 2 * 2 and touched == 2 * 2 * 2
    assert telemetry.gauge("serving_pool_latent_bytes").value == \
        3 * 64 * 4 * 128 * 4


def test_the_familys_counts_are_the_publisheds_form():
    """8 routed experts and the shared one a token, not all 256; the
    absorbed products are not counted: a hand count at the
    configuration's own widths."""
    from benchmark.harness import Harness
    cfg = Harness().cell(
        "joyai-llm-flash.serve-closed64-decode").config
    attention = 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 \
        + 512 * 32 * 256 + 4096 * 2048
    assert attention == 26345472            # ISSUE 28's count
    expert = 3 * 2048 * 768
    layers = 5 * attention + 3 * 2048 * 7168 \
        + 4 * (256 * 2048 + 9 * expert)
    assert fam.token_matrix_flops(cfg) == 2 * layers
    pair = 5 * 2 * 32 * (192 + 128)
    head = 2 * 2048 * cfg["vocab_size"]
    assert fam.decode_flops(cfg, 1000) == 2 * layers + head \
        + pair * 1000
    assert fam.prefill_flops(cfg, 1000) == 2 * layers * 1000 + head \
        + pair * 1000 * 1001 // 2


# tokens and the first six logits of each request's last step that the
# parent commit's engine (f14f2cb: the isinstance check and the float32
# per-head pools written out in engine.py) gave for this seeded model
# and these prompts, on the CPU; the builders in transformer.py are
# untouched, and the engine now reaches them by the protocol
PARENT = {
    "learned": (
        [[35, 29, 50, 30, 55, 29, 35, 29, 37],
         [55, 29, 19, 37, 49, 22, 37, 46, 28],
         [35, 22, 29, 11, 5, 10, 4, 35, 22],
         [11, 5, 11, 5, 11, 5, 11, 5, 11]],
        [[4.127861499786377, 2.0725791454315186, -2.122150421142578,
          2.0909714698791504, -5.048786640167236, 0.6074051260948181],
         [0.8151566982269287, 0.6203712821006775, -2.9808743000030518,
          0.4944644272327423, -2.8133583068847656, 3.070876359939575],
         [4.427976608276367, -2.8988044261932373, -0.9246521592140198,
          -2.1261017322540283, -0.5407710671424866, 3.1275134086608887],
         [3.504004955291748, 0.45155784487724304, 2.184028148651123,
          0.1483348160982132, -0.9758239388465881, 1.866438865661621]]),
    "rope": (
        [[22, 31, 22, 39, 46, 8, 52, 27, 50],
         [44, 41, 7, 22, 7, 41, 7, 23, 23],
         [22, 24, 37, 22, 21, 28, 7, 28, 39],
         [46, 46, 46, 41, 22, 23, 48, 34, 48]],
        [[1.7054753303527832, 2.4392168521881104, 6.102887153625488,
          -4.749447345733643, -1.7787041664123535, -4.275985240936279],
         [-3.1790335178375244, -3.0909337997436523, -4.630758285522461,
          -0.2430969476699829, -2.0518550872802734, -4.785927772521973],
         [-5.5068559646606445, 2.820974588394165, 2.7584033012390137,
          -2.6087872982025146, 4.316710472106934, 2.8257017135620117],
         [-4.968897342681885, -3.575322389602661, -6.995070934295654,
          2.4260094165802, -1.0687720775604248, -2.95535945892334]])}


@pytest.fixture(scope="module")
def served_as_the_parent_did():
    """Both models drawn and served in the parent's order (serving
    draws from the same random stream as the initializers)."""
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    mx.random.seed(11)
    out = {}
    for pos, kv in (("learned", None), ("rope", 2)):
        lm = TransformerLM(61, d_model=32, n_layers=2, n_heads=4,
                           max_len=64, pos=pos, n_kv_heads=kv)
        lm.initialize(mx.initializer.Normal(0.5))
        eng = ServingEngine(lm, max_batch=3, block_size=4,
                            num_blocks=48, keep_logits=True)
        rs = np.random.RandomState(5)
        reqs = [eng.submit(rs.randint(0, 61, n), 9)
                for n in (17, 5, 30, 11)]
        eng.run()
        out[pos] = (eng, reqs)
    return out


@pytest.mark.parametrize("pos,kv", [("learned", None), ("rope", 2)])
def test_transformer_lm_serves_what_the_parent_served(
        served_as_the_parent_did, pos, kv):
    eng, reqs = served_as_the_parent_did[pos]
    # its cache: keys and values, float32, as before; since PR 29
    # every kv head's side by side in one row of whole lanes
    assert [c["name"] for c in eng.cache_spec] == ["k", "v"]
    assert [(a.shape, str(a.dtype)) for pool in eng._pools
            for a in pool] == [((48, 4, (kv or 4) * 8), "float32")] * 4
    assert eng.max_len == 64 and eng.max_blocks == 16
    tokens, logits = PARENT[pos]
    assert [r.generated for r in reqs] == tokens
    # equal to the last digit on the machine they were taken on; a
    # few units in the last place are left for another CPU's sums
    np.testing.assert_allclose(
        [np.asarray(r.logits, np.float32).reshape(-1)[:6]
         for r in reqs], np.asarray(logits, np.float32),
        rtol=0, atol=2e-6)
