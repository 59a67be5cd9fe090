"""What one call of the Mamba-2 state-step kernel (``ssm_state_step``,
incubator_mxnet_tpu/ops/ssm.py) must move: every slot's state read
once and written once (float32, heads x head_dim x d_state a slot),
the step's rows ``x`` (heads x head_dim), ``dt`` (heads), ``B`` and
``C`` (n_groups x d_state each) read and ``y`` (heads x head_dim)
written, all float32 as the kernel takes them.  Its operations (some
five a state element: decay, update, read) are elementwise and never
bind on this chip; the roofline is the bytes'."""


def state_step_bytes(cfg, slots):
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    state = heads * p * n
    rows = 2 * heads * p + heads + 2 * groups * n
    return 4 * slots * (2 * state + rows)


def state_step_flops(cfg, slots):
    return 5 * slots * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"]
