"""Family ``other_lm``: a family that is not ``transformer_lm``, added
as files in a directory of its own (tests/benchmark_tests/).

The program behind it is still ``TransformerLM`` (the program has no
other block yet), but everything the harness learns of it comes from
this file: the configuration names its widths as most published
models do (``intermediate_size``, no ``ffn_dim``), states weights in
bfloat16, and the family counts its own work from those keys.  Its
reference is the plain transformer of benchmark/reference/ under this
family's key names.
"""
from ..reference import transformer as ref


def _reference_cfg(cfg):
    return {**cfg, "ffn_dim": cfg["intermediate_size"]}


def param_shapes(cfg):
    return ref.param_shapes(_reference_cfg(cfg))


def reference_logits(params, tokens, cfg, mode="f32"):
    return ref.logits(params, tokens, _reference_cfg(cfg), mode)


def build_program(mx, cfg, ctx, grad_req=None, dtype="float32"):
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    lm = TransformerLM(
        cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        max_len=cfg["max_position_embeddings"],
        mlp_ratio=cfg["intermediate_size"] // cfg["hidden_size"],
        dropout=0.0)
    if grad_req:
        lm.collect_params().setattr("grad_req", grad_req)
    lm.cast(dtype)
    lm.initialize(mx.initializer.Zero(), ctx=ctx)
    return lm


def example_args(mx, cfg, ctx):
    return [mx.nd.zeros((1, 16), ctx=ctx, dtype="int32")]


def _block_flops(cfg, rows):
    """The blocks' matrices over ``rows`` positions: a multiply-add
    is two operations."""
    d, inner = cfg["hidden_size"], cfg["intermediate_size"]
    return 2 * rows * cfg["num_hidden_layers"] \
        * (4 * d * d + 2 * d * inner)


def _head_flops(cfg):
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(cfg, length):
    """A prompt of ``length`` to its first token: the blocks over
    every position, causal scores and values over the visible pairs
    (``length * (length + 1) / 2``), the head over the last position
    alone."""
    pairs = length * (length + 1) // 2
    return _block_flops(cfg, length) + _head_flops(cfg) \
        + cfg["num_hidden_layers"] * 4 * cfg["hidden_size"] * pairs


def decode_flops(cfg, context):
    """One new token against ``context`` positions, itself among
    them."""
    return _block_flops(cfg, 1) + _head_flops(cfg) \
        + cfg["num_hidden_layers"] * 4 * cfg["hidden_size"] * context
