"""Family ``mhc_latent_moe_lm``: the program's ``LatentMoELM`` with a
hyper-connection residual of ``hc_mult`` streams and YaRN positions
(Xing4.0's layer), built from its config.json, and its plain reference
(reference/mhc_latent_moe.py).  Served only: it has no training
functions.
"""
from ..reference import mhc_latent_moe as ref
from . import latent_moe_lm as latent
from .latent_moe_lm import build_program, example_args  # noqa: F401

param_shapes = ref.param_shapes
reference_logits = ref.logits


# ------------------------------------------------------------------
# What serving a request requires, for ``step_mfu.serve``: the latent
# layer's count (models/latent_moe_lm.py), and per token and sublayer
# the hyper-connection's products by flops.py's rules: the mapping,
# one row of n*C against n + n + n*n columns, 2 nC (2n + n^2); the
# mixes, H_res over the streams and h_pre's and h_post's weighted
# rows, 2 n^2 C + 4 nC.  The elementwise Sinkhorn rounds and the
# norm are not counted, as no family counts norms.
# ------------------------------------------------------------------


def hyper_flops(cfg):
    """Operations of one position through every hyper-connection."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    one = 2 * n * c * (2 * n + n * n) + 2 * n * n * c + 4 * n * c
    return 2 * cfg["num_hidden_layers"] * one


def prefill_flops(cfg, length):
    return latent.prefill_flops(cfg, length) + hyper_flops(cfg) * length


def decode_flops(cfg, context):
    return latent.decode_flops(cfg, context) + hyper_flops(cfg)
