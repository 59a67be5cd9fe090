"""The yardstick: operations and bytes counted from shapes."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import flops  # noqa: E402
from benchmark.harness import HERE  # noqa: E402
from benchmark.reference import transformer  # noqa: E402

OPT = json.load(open(os.path.join(HERE, "configs", "opt-1.3b.json")))


def test_lm_train_flops_are_six_n_t_plus_causal_attention():
    batch, length = 2, 2048
    n = flops.lm_matmul_params(OPT)
    shapes = transformer.param_shapes(OPT)
    matrices = sum(s[0] * s[1] for name, (s, kind) in shapes.items()
                   if kind == "matrix" and "embedding" not in name)
    assert n == matrices
    total = flops.lm_train_flops(OPT, batch, length)
    six_nt = 6 * n * batch * length
    attention = total - six_nt
    # causal attention: 3 x 2 x d x L(L+1) a layer and sequence
    assert attention == 3 * OPT["num_hidden_layers"] * batch * 2 \
        * OPT["hidden_size"] * length * (length + 1)
    assert 0.05 < attention / total < 0.08
    assert abs(total - 13.27e12) / 13.27e12 < 0.01


def test_causal_attention_counts_visible_pairs():
    d = 64
    assert flops.causal_attention_flops(1, d) == 4 * d
    assert flops.causal_attention_flops(4, d) == 4 * d * 10
    # one new token against a context of 10, itself included
    assert flops.causal_attention_flops(1, d, kv_len=10) == 4 * d * 10
    # prefill of 4 then 2 decodes is the same work as 6 at once
    whole = flops.causal_attention_flops(6, d)
    parts = flops.causal_attention_flops(4, d) + sum(
        flops.causal_attention_flops(1, d, kv_len=n) for n in (5, 6))
    assert whole == parts


def test_serving_flops_add_up_to_the_forward_pass():
    plen, new = 300, 5
    served = flops.lm_prefill_flops(OPT, plen) + sum(
        flops.lm_decode_flops(OPT, plen + i) for i in range(1, new))
    head = 2 * OPT["hidden_size"] * OPT["vocab_size"]
    whole = flops.lm_forward_flops(OPT, plen + new - 1)
    # the forward pass runs the head on every position, serving only
    # where a token is wanted
    assert served == whole - head * (plen - 1)


def test_flash_cost_and_roofline():
    n_flops, n_bytes = flops.flash_train_flops_bytes(OPT, 2, 2048)
    per_layer_seq = flops.causal_attention_flops(2048, 2048)
    assert n_flops == 3 * 8 * 2 * per_layer_seq
    assert n_bytes == 8 * 12 * 2 * 2048 * 2048 * 2
    least, bound = flops.roofline_seconds(n_flops, n_bytes, 197e12,
                                          819e9)
    assert bound == "flops" and least == pytest.approx(
        n_flops / 197e12)
    assert flops.roofline_seconds(1e6, 1e9, 197e12, 819e9)[1] == "bytes"
