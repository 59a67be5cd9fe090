"""What decides ``correct``: the references agree with the program at
a small size, the control (the reference one precision down, put in
the program's place) comes out as not correct, and so does a run
whose timed path is broken underneath.  The sizes are the rehearsal's
(benchmark/testdata/rehearse); the limits there were set, as the
cells' own, between the program's readings and the control's."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import serve, train  # noqa: E402

# ------------------------------------------------------------ serving
def test_transformer_reference_agrees_with_transformer_lm(harness):
    """Logits of the program's model and of the plain reference on
    the same seeded weights."""
    import jax.numpy as jnp

    import incubator_mxnet_tpu as mx
    from benchmark import weights
    cell = harness.cell("tiny-lm.serve")
    fam = harness.family(cell.config)
    shapes = fam.param_shapes(cell.config)
    block = train.settled_block(fam, mx, cell.config, mx.tpu(0), shapes,
                                9, trained=False)
    toks = np.random.RandomState(0).randint(
        0, cell.config["vocab_size"], (2, 128)).astype(np.int32)
    mine = block.forward(mx.nd.array(toks, dtype="int32")).asnumpy()
    params = weights.make(shapes, 9)
    ref = np.asarray(fam.reference_logits(params, jnp.asarray(toks),
                                          cell.config))
    assert np.abs(mine - ref).max() < 2e-4 * np.abs(ref).max()
    low = np.asarray(fam.reference_logits(params, jnp.asarray(toks),
                                          cell.config, "bf16"))
    assert np.abs(low - ref).max() > 20 * np.abs(mine - ref).max()


def test_a_sound_run_of_the_serve_cell_is_correct(measure):
    result = measure("tiny-lm.serve", seconds=0.3)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 3 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "setup_s", "serve_tok_per_s", "itl_p95_ms"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, measure):
    from incubator_mxnet_tpu.serving import ServingEngine
    sound = ServingEngine._append_token

    def altered(self, req, tok, events):
        if len(req.generated) == 2:          # every request's third
            tok = (tok + 1) % self.model.head._units
        return sound(self, req, tok, events)

    monkeypatch.setattr(ServingEngine, "_append_token", altered)
    result = measure("tiny-lm.serve", seconds=0.3)
    assert not result["correct"], result["compared"]
    row = result["compared"]["far_gap_share"]
    assert row["value"] > 10 * row["limit"]
    assert result["compared"]["token_gap"]["value"] > 0.5


def test_serve_control_one_precision_down_is_not_correct(harness):
    """The tokens that the bfloat16 reference puts first, read as if
    they had been served, lose about what the yardstick expects the
    control to lose: a share about 1, over the limit.  The reference's
    own tokens read 0."""
    from benchmark import correct
    cell = harness.cell("tiny-lm.serve")
    cfg = cell.config
    fam = harness.family(cfg)
    assert cfg["serve"]["control_precision"] == "bf16"
    rs = np.random.RandomState(4)
    sample = [(rs.randint(0, 256, 60).astype(np.int32),
               rs.randint(0, 256, 40).astype(np.int32))
              for _ in range(48)]
    numbers = serve.gaps(fam, cfg, 4, sample, of_control=True)
    assert 0.6 < numbers["far_gap_share"][0] < 2
    assert numbers["far_gap_share"][1].startswith(
        "1920 tokens of 48 requests; 6 gaps beyond ")
    assert numbers["token_gap"][0] > 0
    assert not correct.verdict(numbers, cell.limits)[0], numbers
    # random tokens lie far below the best: a share in the thousands
    served = serve.gaps(fam, cfg, 4, sample)
    assert served["far_gap_share"][0] > 1e3


@pytest.mark.parametrize("least_flips,share", [
    (0.0, 4.0), (0.5, 3.5777088), (4.0, 0.4472136)])
def test_far_gap_share_counts_gaps_beyond_the_controls_noise(
        least_flips, share):
    """By hand: the control's errors are -0.3, -0.1, 0.1, 0.3 at four
    positions (noise: root mean square 0.2236), so a margin of 0.05 is
    turned by half of them and one of 0.2 by a quarter.  Beyond half a
    sigma (0.1118) only the margin of 0.2 counts: the yardstick is
    0.2 * 0.25 = 0.05 over a quarter of a turn.  Of the served gaps,
    0.05 and 0.2, the first is nearer than the noise and counts for
    nothing.  The yardstick is never under ``least_flips`` turns of
    0.1118."""
    gap = np.array([0.0, 0.05, 0.2, 0.0])
    margin = np.array([1.0, 0.05, 0.2, 1.0])
    error = np.array([-0.3, -0.1, 0.1, 0.3])
    got, where = serve.far_gap_share(gap, margin, error, 0.5,
                                     least_flips)
    assert got == pytest.approx(share, rel=1e-6)
    assert where.startswith("1 gaps beyond 0.112; the control's "
                            "yardstick 0.05 over 0.25 turns")


def test_reference_at_default_precision_is_float32_off_the_chip(harness):
    """``f32_default`` leaves the products to the platform: on the CPU
    that is float32, so it agrees with ``f32`` to rounding (on a TPU
    it is one bf16 pass, which is what the serve configuration
    states)."""
    import jax.numpy as jnp
    from benchmark import weights
    cfg = harness.cell("tiny-lm.serve").config
    fam = harness.family(cfg)
    params = weights.make(fam.param_shapes(cfg), 6)
    toks = jnp.asarray(np.random.RandomState(1).randint(
        0, cfg["vocab_size"], (1, 64)).astype(np.int32))
    exact = np.asarray(fam.reference_logits(params, toks, cfg, "f32"))
    stated = np.asarray(fam.reference_logits(params, toks, cfg,
                                             "f32_default"))
    assert np.abs(stated - exact).max() < 1e-5 * np.abs(exact).max()


def test_traffic_gives_every_seed_the_same_sizes_in_another_order(
        harness):
    from benchmark.traffic import Plan, prefill_buckets, size_pool
    traffic = harness.cell("tiny-lm.serve").traffic
    pool = size_pool(traffic)
    assert len(pool) == traffic["pool_requests"]
    assert min(p for p, _ in pool) >= 8 and max(p for p, _ in pool) <= 100
    a, b = Plan(traffic, 256, 1), Plan(traffic, 256, 2 ** 31 + 5)
    sizes = lambda plan: [(len(t), n) for t, n in  # noqa: E731
                          (plan.next() for _ in range(len(pool)))]
    sa, sb = sizes(a), sizes(b)
    assert sorted(sa) == sorted(sb) == sorted(pool) and sa != sb
    again, same = Plan(traffic, 256, 1), Plan(traffic, 256, 1)
    assert [again.next()[0].tolist() for _ in range(3)] \
        == [same.next()[0].tolist() for _ in range(3)]
    assert prefill_buckets(traffic, 16, 256) == [16, 32, 64, 128]


def test_the_serve_cell_warms_the_buckets_its_pool_reaches():
    from benchmark.harness import Harness
    from benchmark.traffic import prefill_buckets, size_pool
    traffic = Harness().cell("opt-1.3b.serve-closed16").traffic
    pool = size_pool(traffic)
    # few enough sizes that ramp and window go through all of them
    assert len(pool) == 32 and len(set(pool)) == 32
    assert prefill_buckets(traffic, 16, 2048) == [256, 512, 1024, 2048]
