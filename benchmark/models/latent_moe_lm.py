"""Family ``latent_moe_lm``: the program's ``LatentMoELM`` (latent
attention, routed experts with a shared one, leading dense layers)
built from a DeepSeek-V3-style config.json, and its plain reference.
Served only: it has no training functions.
"""
from ..reference import latent_moe as ref

param_shapes = ref.param_shapes
reference_logits = ref.logits


def build_program(mx, cfg, ctx, grad_req=None, dtype="float32"):
    """The program's own constructor, from the file's keys.  The
    Parameters are cast to ``dtype`` before they are initialized."""
    from incubator_mxnet_tpu.gluon.model_zoo.latent_moe import \
        LatentMoELM
    lm = LatentMoELM(cfg)
    if grad_req:
        lm.collect_params().setattr("grad_req", grad_req)
    lm.cast(dtype)
    lm.initialize(mx.initializer.Zero(), ctx=ctx)
    return lm


def example_args(mx, cfg, ctx):
    """A short row: every shape is known from the config already."""
    return [mx.nd.zeros((1, 16), ctx=ctx, dtype="int32")]


# ------------------------------------------------------------------
# What serving a request requires, for ``step_mfu.serve``; flops.py's
# rules: a multiply-add is two operations, what the mathematics
# requires and in its published form whatever the program runs.
# Every matrix a token passes through counts twice its parameters:
# ``num_experts_per_tok`` routed experts and the shared ones, not all
# ``n_routed_experts``.  Attention is counted expanded: each token
# that enters a context has its keys and values expanded once (that
# is ``kv_b`` among the token's matrices), scores run over qk_nope +
# qk_rope dims and values over v_head_dim a head and visible
# position.  The absorbed form's extra multiplies at decode (scores
# and values over kv_lora_rank) are the program's choice and are not
# counted, so ``step_mfu.serve`` cannot rise by them.
# ------------------------------------------------------------------


def token_matrix_flops(cfg):
    """Operations of one position through every layer's matrices,
    the head left out."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    attention = d * cfg["q_lora_rank"] \
        + cfg["q_lora_rank"] * heads * (nope + rope) \
        + d * (cfg["kv_lora_rank"] + rope) \
        + cfg["kv_lora_rank"] * heads * (nope + cfg["v_head_dim"]) \
        + heads * cfg["v_head_dim"] * d
    expert = 3 * d * cfg["moe_intermediate_size"]
    routed = cfg["n_routed_experts"] * d + expert * (
        cfg["num_experts_per_tok"] + cfg["n_shared_experts"])
    n_dense = min(cfg["first_k_dense_replace"],
                  cfg["num_hidden_layers"])
    return 2 * (cfg["num_hidden_layers"] * attention
                + n_dense * 3 * d * cfg["intermediate_size"]
                + (cfg["num_hidden_layers"] - n_dense) * routed)


def _pair_flops(cfg):
    """One query against one visible position, all heads and
    layers: the score and the weighted value."""
    return cfg["num_hidden_layers"] * 2 * cfg["num_attention_heads"] \
        * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
           + cfg["v_head_dim"])


def head_flops(cfg):
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(cfg, length):
    """A prompt of ``length`` up to its first token: the layers over
    every position, attention over the visible pairs, the head over
    the last position alone."""
    return token_matrix_flops(cfg) * length + head_flops(cfg) \
        + _pair_flops(cfg) * (length * (length + 1) // 2)


def decode_flops(cfg, context):
    """One new token whose context, itself included, is
    ``context``."""
    return token_matrix_flops(cfg) + head_flops(cfg) \
        + _pair_flops(cfg) * context
