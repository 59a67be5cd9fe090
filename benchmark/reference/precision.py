"""The precisions a reference can be computed in.

``f32`` is the reference proper: float32 operands, every product at
``highest`` precision.  ``f32_default`` is the reference of a
configuration that states float32 at ``matmul_precision: default``:
float32 operands and activations, every product at the platform's
default precision, which on a TPU is one bf16 pass (operands rounded
to bfloat16 in the multiplier, float32 accumulation) and on the CPU
plain float32.  The others are the controls of "How correct is
decided": the reference put in the program's place, one precision
below what the configuration states.

- ``bf16``: weights and the activations between operations in
  bfloat16, products accumulated in float32, statistics (layer norm,
  softmax) in float32 -- what a bf16 deployment of a float32
  configuration would do.
- ``fp8``: the same one step further down, as fp8 training recipes
  have it: weights and the activations between operations rounded to
  float8_e4m3, the gradients that flow back through them to
  float8_e5m2, each with one scale per tensor; products accumulated in
  float32, statistics in float32.
"""
import jax
import jax.numpy as jnp

_E4M3_MAX = 448.0
_E5M2_MAX = 57344.0


def _scaled_round(x, dtype, largest):
    scale = largest / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(x.dtype) / scale


@jax.custom_vjp
def _fp8_round(x):
    return _scaled_round(x, jnp.float8_e4m3fn, _E4M3_MAX)


def _fp8_round_fwd(x):
    return _fp8_round(x), None


def _fp8_round_bwd(_, g):
    return (_scaled_round(g, jnp.float8_e5m2, _E5M2_MAX),)


_fp8_round.defvjp(_fp8_round_fwd, _fp8_round_bwd)


def operand(x, mode):
    """An operand of a product, as ``mode`` stores it."""
    if mode == "fp8":
        return _fp8_round(x)
    if mode == "bf16":
        return x.astype(jnp.bfloat16)
    return x


def carried(x, mode):
    """An activation as ``mode`` carries it between operations."""
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "fp8":
        return _fp8_round(x)
    return x


def einsum(spec, a, b, mode):
    """``jnp.einsum`` of two operands in ``mode``, float32 result."""
    out = jnp.einsum(spec, operand(a, mode), operand(b, mode),
                     precision=None if mode == "f32_default"
                     else "highest",
                     preferred_element_type=jnp.float32)
    return carried(out, mode)
