"""``LatentMoELM`` with hyper-connections (``hc_mult`` streams, mixing
matrices projected by Sinkhorn-Knopp) and YaRN positions, Xing4.0's
layer, against the plain reference (benchmark/reference/
mhc_latent_moe.py) at a small size on the CPU: the eager forward,
prefill then decode through ``ServingEngine``'s latent pool, the
mixing matrices, the YaRN table, the planted faults, and a model
without hyper-connections left as it was.  Weights are seeded
(benchmark/weights.py, the harness's own path); each tolerance says
why."""
import json
import math
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax.numpy as jnp  # noqa: E402

import incubator_mxnet_tpu as mx  # noqa: E402
from benchmark import train, weights  # noqa: E402
from benchmark.models import mhc_latent_moe_lm as fam  # noqa: E402
from benchmark.reference import mhc_latent_moe as ref  # noqa: E402
from incubator_mxnet_tpu import telemetry, tracing  # noqa: E402
from incubator_mxnet_tpu.gluon.model_zoo.latent_moe import \
    LatentMoELM  # noqa: E402
from incubator_mxnet_tpu.serving import ServingEngine  # noqa: E402

from test_latent_moe import CFG as LATENT, _chained_against_the_forward, \
    _gap, _served_logits  # noqa: E402

# hidden 64, 4 streams, 4 heads, latent 16 + rope 8, 4 experts top 2,
# one dense and one expert layer; YaRN as the configuration has it
CFG = dict(LATENT, family="mhc_latent_moe_lm", n_routed_experts=4,
           num_hidden_layers=2, hc_mult=4, hc_sinkhorn_iters=20,
           hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
           rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                         "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096})
SEED = 2147483677          # over 2**31, as the driver's seeds are

# The program against the reference's full forward: per position the
# widest gap between logits, over the spread of the reference's logits.
#   float32: the same products and sums in another order, the Sinkhorn
#     rounds over four streams included: some units in the last place
#     over two layers.  Held at every position.
#   bfloat16: the program carries bfloat16 between operations and
#     keeps its streams in bfloat16 (8 bits of mantissa), as the
#     latent model's test has it; held at the median position (a
#     token whose 2nd and 3rd router scores lie within a rounding goes
#     to another expert).  The reference in fp8 (4 bits) on the same
#     leaves has to fail the same tolerance twice over.
TOL = {"float32": 2e-5, "bfloat16": 0.04}
OVER = {"float32": np.max, "bfloat16": np.median}


def _built(dtype, cfg=CFG):
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


def test_prefill_then_eight_decode_steps_through_the_latent_pool(built):
    """What ``ServingEngine`` serves (expanded prefill into the paged
    latent pool, absorbed decode out of it, the streams carried
    through both) against the reference's full forward over prompt and
    served tokens: a prefill, then 8 decode steps a request, compared
    as logits.  The engine says its model's streams on its
    ``serve_paged_read`` event, and the decode steps' largest marginal
    error comes back behind the tokens as a gauge."""
    dtype, block, params = built
    eng = ServingEngine(block, max_batch=3, block_size=4,
                        num_blocks=80, keep_logits=True, max_len=96)
    prompts = [t[:n] for t, n in zip(_tokens(3, 50, 1), (50, 23, 37))]
    before = len(tracing.events("serve_paged_read"))
    reqs, got = _served_logits(eng, prompts, 9)
    event, = tracing.events("serve_paged_read")[before:]
    assert event["residual_streams"] == 4
    error = telemetry.gauge("serving_hc_marginal_error").value
    assert eng._m_hc is not None and 0 <= error < 0.5
    for r, prompt in zip(reqs, prompts):
        assert r.state == "finished" and len(r.generated) == 9
        seq = np.concatenate([prompt, r.generated])[None]
        want = np.asarray(ref.logits(params, seq, CFG, "f32"))[0]
        at = len(prompt) - 1 + np.arange(9)
        served = np.stack(got[r.id][-8:])
        assert _gap(served, want[at[1:]], OVER[dtype]) < TOL[dtype]
        if dtype == "float32":
            assert list(np.argmax(want[at], -1)) == r.generated
        else:
            low = np.asarray(ref.logits(params, seq, CFG, "fp8"))[0]
            assert _gap(low[at[1:]], want[at[1:]],
                        np.median) > 2 * TOL[dtype]


def test_the_chained_loop_serves_the_forwards_tokens(f32):
    """The four-stream residual through the chained decode loop:
    admissions while a step is in flight, budgets that end in flight;
    float32, every served token is the eager forward's."""
    _chained_against_the_forward(f32[0])


@pytest.mark.parametrize("spread,rows", [(0.5, 3e-6), (2.4, 0.1),
                                         (100.0, 2.0)])
def test_the_mixing_matrices_are_the_references_rounds(f32, spread, rows):
    """The program's 20 rounds entry by entry against the reference's
    over whole matrices, on logits clipped at +-30: the same matrices
    to float32's rounding.  Each round ends with the columns, so every
    column sums to 1 within a few hc_eps whatever the logits.  The
    rows converge within hc_eps-sized error where the logits spread
    little; where they spread as the configuration's do (about 2.4)
    a few tokens in ten thousand are still some hundredths off after
    20 rounds, and at the clip's spread whole rows are: what
    ``serving_hc_marginal_error`` reads on the chip."""
    block, _ = f32
    rs = np.random.RandomState(3)
    z = np.clip(rs.randn(4096, 16) * spread, -30, 30).astype(np.float32)
    m = jnp.exp(jnp.asarray(z))
    h, err = block._sinkhorn(m)
    mine = np.stack([np.stack(row, -1) for row in h], 1)
    want = np.asarray(ref.sinkhorn(m.reshape(-1, 4, 4), 20, 1e-6))
    np.testing.assert_allclose(mine, want, rtol=2e-5, atol=1e-30)
    assert np.abs(mine.sum(-2) - 1).max() < 4e-6
    worst = np.abs(np.concatenate([mine.sum(-1), mine.sum(-2)], -1)
                   - 1).max(-1)
    np.testing.assert_allclose(np.asarray(err), worst, atol=1e-6)
    assert worst.max() < rows


def test_the_yarn_table_is_the_equations():
    """inv_freq, low and high and the softmax scale at the
    configuration's own keys (d = 64, theta = 10000, s = 64, L0 =
    4096), written out here from the equations: the program's and the
    reference's alike."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        cfg = json.load(f)
    d, theta, s, l0 = 64, 10000.0, 64.0, 4096.0

    def corr(b):
        return d * math.log(l0 / (2 * math.pi * b)) / (2 * math.log(theta))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    table = []
    for i in range(32):
        f = theta ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        table.append(f / s * ramp + f * (1 - ramp))
    scale = 192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2
    assert scale == pytest.approx(0.14468, abs=1e-5)
    lm = LatentMoELM(cfg)
    np.testing.assert_allclose(lm._inv_freq, table, rtol=1e-6)
    assert lm.softmax_scale == pytest.approx(scale, rel=1e-12)
    mine, theirs = ref.yarn(cfg)
    np.testing.assert_allclose(mine, table, rtol=1e-6)
    assert theirs == pytest.approx(scale, rel=1e-12)
    # the first eleven frequencies are theta's, from 23 on a 64th
    np.testing.assert_array_equal(lm._inv_freq[:11],
                                  np.float32(table[:11]))
    assert lm._inv_freq[23] == pytest.approx(
        theta ** (-2 * 23 / d) / s, rel=1e-6)


@pytest.mark.parametrize("scaling", [
    {"type": "linear", "factor": 2.0},
    {"type": "yarn", "factor": 64, "mscale": 1, "mscale_all_dim": 0,
     "original_max_position_embeddings": 4096}],
    ids=["linear", "yarn-cos-scaled"])
def test_other_rope_scaling_is_refused(scaling):
    with pytest.raises(ValueError, match="not built"):
        LatentMoELM(dict(LATENT, rope_scaling=scaling))


def _at_width(params):
    """The leaves scaled so that what they make spreads as at the
    configuration's widths, where a row of N(0, 0.02) weights meets
    thousands of inputs and not dozens: a hyper-connection's logits by
    sqrt(4 * 3584 / (4 * 64)) (about 2.4 there, so H_res far from
    uniform), k_pe by sqrt(3584 / 64), queries and keys by sqrt(768 /
    24) and sqrt(512 / 16) (scores spread by about 0.5)."""
    scale = {"hc_attn_weight": 7.48, "hc_ffn_weight": 7.48,
             "kv_a_weight": 7.48, "q_b_weight": 5.66, "kv_b_weight": 5.66}
    return {n: v * scale.get(n.split("_", 1)[1], 1.0)
            for n, v in params.items()}


@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_a_planted_fault_moves_the_references_logits(f32, fault):
    """Each planted fault removes one part of what this layer adds to
    the latent one: over 128 positions (YaRN's ramp turns frequencies
    that a shorter text barely rotates by) and leaves that spread as
    at the configuration's widths it moves the logits by more than the
    bfloat16 tolerance, at the median position past the first few."""
    params = _at_width(f32[1])
    toks = _tokens(1, 128, 9)
    want = np.asarray(ref.logits(params, toks, CFG, "f32"))
    bad = np.asarray(ref.logits(params, toks, CFG, "f32", fault=fault))
    assert _gap(bad[0, 8:], want[0, 8:], np.median) > TOL["bfloat16"]


def test_without_hc_mult_no_hyper_connection_is_built():
    """The latent model as before: no Parameter, stream or gauge of a
    hyper-connection, one residual stream on the event, and the decode
    step's statistics the four of the routed layers."""
    lm = LatentMoELM(LATENT)
    assert lm.residual_streams == 1
    assert not [n for n in lm.collect_params() if "hc_" in n]
    assert lm._paged_read(4, "cpu")["residual_streams"] == 1
    assert lm._decode_workspace_bytes(2) == 4.0 * 2 * (97 + 8 * 64)
    mine = LatentMoELM(CFG)
    assert sorted(n.split("layer0_")[1] for n in mine.collect_params()
                  if "layer0_hc_" in n) == [
        "hc_attn_alpha", "hc_attn_bias", "hc_attn_weight",
        "hc_ffn_alpha", "hc_ffn_bias", "hc_ffn_weight"]
    assert mine._decode_workspace_bytes(2) == 4.0 * 2 * (97 + 32 * 64)
    lm.initialize(mx.initializer.Normal(0.02))
    eng = ServingEngine(lm, max_batch=2, block_size=4, num_blocks=32)
    eng.submit(_tokens(1, 9, 4)[0], 3)
    eng.run()
    assert eng._m_hc is None


def test_the_familys_counts_are_the_latent_ones_and_the_mixes():
    """The configuration's counts by hand: the latent layer's (4
    routed experts and the shared one a token, attention expanded) and
    per token and sublayer the hyper-connection's mapping and mixes."""
    from benchmark.harness import Harness
    cfg = Harness().cell("xing4.0-29b-a4b.serve-closed64-decode").config
    attention = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 \
        + 512 * 32 * 256 + 4096 * 3584
    expert = 3 * 3584 * 1024
    layers = 6 * attention + 2 * 3 * 3584 * 9216 \
        + 4 * (64 * 3584 + 5 * expert)
    hyper = 6 * 2 * (2 * 14336 * 24 + 2 * 16 * 3584 + 4 * 14336)
    head = 2 * 3584 * 32768
    pair = 6 * 2 * 32 * (192 + 128)
    assert fam.decode_flops(cfg, 1000) == 2 * layers + hyper + head \
        + pair * 1000
    assert fam.prefill_flops(cfg, 1000) == (2 * layers + hyper) * 1000 \
        + head + pair * 1000 * 1001 // 2
