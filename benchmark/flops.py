"""Operations and bytes that the algorithms need, from shapes alone.

The yardstick of every ``*_mfu`` and ``*_roofline`` metric: counted
here, under the benchmark's own paths, so that no later PR moves it.
A multiply-add is two operations.  Recomputation (remat, the flash
kernel's second pass over the scores) is not counted: these are the
operations the mathematics requires, not the ones a program issues.

What is here is the count of facebook/opt's block (``ffn_dim``, four
``d x d`` matrices a layer, scores ``d`` wide).  A family with another
block brings its own count in its own file (models/<family>.py:
``train_flops``, ``prefill_flops``, ``decode_flops``), beside the
family and under the benchmark's paths like this file, and held to
the same rules; ``transformer_lm`` gives the functions below.

Copied arithmetic, originals left in place for a later PR to delete
(PERF.md, Open questions): ``TransformerLM.train_flops_per_token`` /
``decode_flops_per_token`` (which count the full, not the causal,
score matrix).
"""


def lm_matmul_params(cfg):
    """Parameters that sit in a matrix every token is multiplied by:
    the blocks' four matrices and the output head (embeddings are
    looked up, not multiplied)."""
    d, ffn = cfg["hidden_size"], cfg["ffn_dim"]
    per_layer = 3 * d * d + d * d + 2 * d * ffn
    return cfg["num_hidden_layers"] * per_layer \
        + d * cfg["vocab_size"]


def causal_attention_flops(length, d_model, kv_len=None):
    """Forward operations of one layer's causal attention over one
    sequence: scores and weighted values, each ``2 * d`` a pair of
    query and visible key.  With ``kv_len`` the queries are the last
    ``length`` positions of a context of ``kv_len``."""
    kv_len = length if kv_len is None else kv_len
    first = kv_len - length + 1          # keys the first query sees
    pairs = length * (first + kv_len) // 2
    return 2 * 2 * d_model * pairs


def lm_forward_flops(cfg, length, kv_len=None):
    """Forward operations for ``length`` new positions of one
    sequence (whose context ends at ``kv_len``, default ``length``)."""
    return 2 * lm_matmul_params(cfg) * length \
        + cfg["num_hidden_layers"] * causal_attention_flops(
            length, cfg["hidden_size"], kv_len)


def lm_train_flops(cfg, batch, length):
    """Forward + backward of one step: three times the forward."""
    return 3 * batch * lm_forward_flops(cfg, length)


def flash_train_flops_bytes(cfg, batch, length, dtype_bytes=2):
    """Operations and HBM bytes of all the layers' causal attention in
    one training step, forward + backward (backward twice forward).
    Bytes: the forward reads q, k, v and writes o; the backward reads
    q, k, v, o, do and writes dq, dk, dv, each (B, L, d)."""
    layers, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    flops = 3 * layers * batch * causal_attention_flops(length, d)
    tensor = batch * length * d * dtype_bytes
    return flops, layers * (4 + 8) * tensor


def roofline_seconds(flops, nbytes, peak_flops, peak_bytes_per_s):
    """The least time the chip could take, and which peak binds."""
    t_flops, t_bytes = flops / peak_flops, nbytes / peak_bytes_per_s
    return (t_flops, "flops") if t_flops >= t_bytes \
        else (t_bytes, "bytes")


def lm_prefill_flops(cfg, length):
    """Forward operations to take in a prompt of ``length`` and give
    the first token: the blocks over every position, the head over the
    last one alone."""
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    blocks = lm_matmul_params(cfg) - d * vocab
    return 2 * blocks * length + 2 * d * vocab \
        + cfg["num_hidden_layers"] * causal_attention_flops(length, d)


def lm_decode_flops(cfg, context):
    """Forward operations for one new token whose context, itself
    included, is ``context`` positions."""
    return lm_forward_flops(cfg, 1, kv_len=context)
