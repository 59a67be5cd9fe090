"""Driver-config example workloads with convergence gates:
config 2 (image classification, mesh path),
config 3 (bucketed LSTM perplexity), config 4 (SSD detection mAP);
the transformer_lm gates are in test_examples_lm.py.

Each example runs in --quick mode, which asserts its own gate
(loss / perplexity / mAP); these tests run them in-process on the
8-device virtual CPU mesh, same as they run unchanged on TPU.
"""
import os
import sys

import numpy as np

_EXAMPLES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples")
if _EXAMPLES not in sys.path:
    sys.path.insert(0, _EXAMPLES)


def test_imagenet_synthetic_quick():
    import train_imagenet_synthetic as ex
    summary = ex.main(["--quick"])
    assert summary["final_loss"] < summary["first_loss"] * 0.7
    assert summary["mesh_dp"] == 8  # really trained on the mesh


def test_lstm_bucketing_quick():
    import lstm_bucketing as ex
    summary = ex.main(["--quick"])
    assert summary["final_ppl"] < summary["first_ppl"] * 0.6
    assert summary["final_ppl"] < summary["uniform_ppl"]


def test_ssd_train_quick():
    import ssd_train as ex
    summary = ex.main(["--quick"])
    assert summary["mAP"] > 0.5
    assert summary["final_loss"] < summary["first_loss"] * 0.7


def test_ssd_anchor_scale_8732():
    """Detection kernels at the reference's real SSD300 anchor
    count."""
    import ssd_train as ex
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    ex.anchor_scale_check(mx, nd)


def test_train_mnist_quick():
    """Config 1: MLP on MNIST via the Module API (ref:
    example/image-classification/train_mnist.py)."""
    import train_mnist as ex
    summary = ex.main(["--quick", "--num-epochs", "3"])
    assert summary["val_acc"] > 0.95


def test_linear_classification_quick():
    """Driver config 5 (sparse): row_sparse weight through KVStore
    with O(touched-rows) pulls and lazy store-side SGD."""
    import linear_classification as ex
    summary = ex.main(["--quick"])
    assert summary["final_nll"] < summary["first_nll"] * 0.65
    assert summary["val_acc"] > 0.8
    # the sparse pull must actually be saving traffic
    assert summary["pull_savings"] > 0.25
