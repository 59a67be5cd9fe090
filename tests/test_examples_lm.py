"""The transformer_lm example's --quick gates (plain, sequence-parallel,
MoE), in-process on the 8-device virtual CPU mesh like
tests/test_examples.py, and in a file of their own: a file is what the
driver's ``--dist loadfile`` hands to one worker."""
import os
import sys

_EXAMPLES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples")
if _EXAMPLES not in sys.path:
    sys.path.insert(0, _EXAMPLES)


def test_transformer_lm_quick():
    import transformer_lm as ex
    summary = ex.main(["--quick"])
    assert summary["final_loss"] < summary["first_loss"] * 0.5
    assert "fox" in summary["generated"]


def test_transformer_lm_seq_parallel_quick():
    from incubator_mxnet_tpu.parallel import make_mesh, use_mesh
    import transformer_lm as ex
    with use_mesh(make_mesh(dp=2, sp=4)):
        summary = ex.main(["--quick", "--seq-parallel",
                           "--batch-size", "16"])
    assert summary["final_loss"] < summary["first_loss"] * 0.5


def test_transformer_lm_moe_quick():
    """--moe-experts: the example trains a routed-MoE LM to the same
    convergence gate, on the mesh, with the aux loss in the
    objective."""
    import transformer_lm as ex
    summary = ex.main(["--quick", "--moe-experts", "4"])
    assert summary["final_loss"] < summary["first_loss"] * 0.5
    assert "fox" in summary["generated"]
