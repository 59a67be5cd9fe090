"""Family ``nemotron_h_lm``: the program's ``NemotronHLM`` (Mamba-2
mixers, latent routed experts with ReLU², grouped-query attention, the
layers' kinds from ``hybrid_override_pattern``) built from a
``nemotron_h`` config.json, and its plain reference
(reference/nemotron_h.py).  Served only: it has no training functions.

The file's ``n_routed_experts`` is what one chip of the deployment
holds (experts ``0 ..  n_routed_experts - 1``) and
``published.n_routed_experts`` the router's width: the program is that
chip's rank (``held``), with the shared expert.
"""
from ..reference import nemotron_h as ref

param_shapes = ref.param_shapes
reference_logits = ref.logits


def build_program(mx, cfg, ctx, grad_req=None, dtype="float32"):
    """The program's own constructor, from the file's keys.  The
    Parameters are cast to ``dtype`` before they are initialized."""
    from incubator_mxnet_tpu.gluon.model_zoo.nemotron_h import \
        NemotronHLM
    lm = NemotronHLM(dict(cfg, n_routed_experts=ref.router_width(cfg)),
                     held=(0, cfg["n_routed_experts"]))
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
# Every matrix a token passes through counts twice its parameters.
# A Mamba layer's recurrence, in the form a token at a time: the
# state's update (the decayed state plus dt x B, one multiply-add an
# element of heads x head_dim x d_state) and its read C . h (one more):
# 4 H P N; the conv 2 C K; no elementwise rest (no family counts
# norms).  An expert layer is this chip's part: the router over every
# expert, both latent projections, the shared expert, and of a
# token's ``num_experts_per_tok`` experts the share this chip holds
# (held / published: 22 x 128 / 512 = 5.5 a token).  Attention: q, k,
# v, o, and a score and a weighted value a head and visible position.
# ------------------------------------------------------------------


def _kinds(cfg):
    pattern = cfg["hybrid_override_pattern"]
    return pattern.count("M"), pattern.count("E"), pattern.count("*")


def token_matrix_flops(cfg):
    """Operations of one position through every layer, the head and
    attention's pairs left out."""
    d = cfg["hidden_size"]
    heads, p, groups, n, inner, conv = ref._dims(cfg)
    mamba = d * (inner + conv + heads) + inner * d \
        + conv * cfg["conv_kernel"] + 2 * heads * p * n
    latent, width = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    shared = cfg["moe_shared_expert_intermediate_size"] \
        * cfg.get("n_shared_experts", 1)
    published = ref.router_width(cfg)
    expert = 2 * latent * width
    moe = published * d + 2 * d * latent + 2 * d * shared \
        + expert * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        // published
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    attention = d * (q + 2 * kv) + q * d
    m, e, a = _kinds(cfg)
    return 2 * (m * mamba + e * moe + a * attention)


def _pair_flops(cfg):
    """One query against one visible position, all heads and
    attention layers: the score and the weighted value."""
    return _kinds(cfg)[2] * 2 * 2 * cfg["num_attention_heads"] \
        * cfg["head_dim"]


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
