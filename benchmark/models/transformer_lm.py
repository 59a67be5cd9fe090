"""Family ``transformer_lm``: the program's ``TransformerLM`` built
from a facebook/opt-style config.json, and its plain reference."""
from .. import flops
from ..reference import transformer as ref

param_shapes = ref.param_shapes
reference_loss = ref.loss
reference_logits = ref.logits


def build_program(mx, cfg, ctx, grad_req=None, dtype="float32"):
    """The program's own constructor, from the file's keys.  The
    Parameters are cast to ``dtype`` before they are initialized, as a
    user who loads a checkpoint of that dtype would: no wider copy is
    ever made."""
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import \
        TransformerLM
    d = cfg["hidden_size"]
    if cfg["ffn_dim"] % d:
        raise ValueError("TransformerLM takes a whole mlp_ratio")
    lm = TransformerLM(
        cfg["vocab_size"], d_model=d,
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        max_len=cfg["max_position_embeddings"],
        mlp_ratio=cfg["ffn_dim"] // d, dropout=0.0)
    if grad_req:
        lm.collect_params().setattr("grad_req", grad_req)
    lm.cast(dtype)
    lm.initialize(mx.initializer.Zero(), ctx=ctx)
    return lm


def example_args(mx, cfg, ctx):
    """A short row: it only settles the deferred shapes."""
    return [mx.nd.zeros((1, 128), ctx=ctx, dtype="int32")]


def program_loss(outputs, labels):
    """Mean next-token cross-entropy, float32 statistics (as
    ``chip_smoke.lm_loss``)."""
    import jax
    import jax.numpy as jnp
    logits = outputs[0]
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None],
                                 axis=-1)[..., 0]
    return jnp.mean(lse - picked.astype(jnp.float32))


def train_batches(cfg, traffic, key):
    """``resident_batches`` batches of (tokens, next tokens), all rows
    different: int32 (n, B, L) each.  Traced: runs on the device."""
    import jax
    import jax.numpy as jnp
    n, b, length = (traffic["resident_batches"], traffic["batch"],
                    traffic["seq_len"])
    toks = jax.random.randint(key, (n, b, length + 1), 0,
                              cfg["vocab_size"], jnp.int32)
    return toks[:, :, :-1], toks[:, :, 1:]


def train_flops(cfg, traffic):
    return flops.lm_train_flops(cfg, traffic["batch"],
                                traffic["seq_len"])


# what serving a request requires, for ``step_mfu.serve``: a served
# family counts its own work (serve.py asks for both before set-up)
prefill_flops = flops.lm_prefill_flops
decode_flops = flops.lm_decode_flops


def units_per_step(traffic):
    return traffic["batch"] * traffic["seq_len"], "tokens"
