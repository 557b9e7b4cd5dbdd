"""The precisions of ``correct``: the reference's (float32 at ``highest``)
and the control's (fp8, the nearest precision below the configurations'
bfloat16), one scale a tensor."""

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _scaled_round(x, dtype, top):
    """Round to ``dtype`` under one scale a tensor (its largest magnitude
    goes to ``top``, the type's largest finite value)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


def fake_fp8(x):
    """An fp8 matmul's forward operand: float8 e4m3, scaled a tensor."""
    return _scaled_round(x, jnp.float8_e4m3fn, 448.0)


def fake_int8(x):
    """An int8 matmul's operand: 255 even steps under one scale a
    tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / s) * s


@jax.custom_vjp
def int8_matmul(a, b):
    """A matrix product as an int8 step computes it: operands and the
    incoming gradient int8, int32/float32 accumulation."""
    return jnp.matmul(fake_int8(a), fake_int8(b), precision=HI)


def _int8_matmul_fwd(a, b):
    return int8_matmul(a, b), (a, b)


def _int8_matmul_bwd(res, g):
    return _matmul_grads(fake_int8(res[0]), fake_int8(res[1]), fake_int8(g))


def _matmul_grads(a, b, g):
    da = jnp.matmul(g, jnp.swapaxes(b, -1, -2), precision=HI)
    if b.ndim == 2 and a.ndim > 2:       # a weight shared over the batch
        db = jnp.matmul(a.reshape(-1, a.shape[-1]).T,
                        g.reshape(-1, g.shape[-1]), precision=HI)
    else:
        db = jnp.matmul(jnp.swapaxes(a, -1, -2), g, precision=HI)
    return da, db


int8_matmul.defvjp(_int8_matmul_fwd, _int8_matmul_bwd)


@jax.custom_vjp
def fp8_matmul(a, b):
    """A matrix product as an fp8 step computes it: both operands e4m3
    forward, the incoming gradient e5m2 backward, float32 accumulation."""
    return jnp.matmul(fake_fp8(a), fake_fp8(b), precision=HI)


def _fp8_matmul_fwd(a, b):
    return fp8_matmul(a, b), (a, b)


def _fp8_matmul_bwd(res, g):
    return _matmul_grads(fake_fp8(res[0]), fake_fp8(res[1]),
                         _scaled_round(g, jnp.float8_e5m2, 57344.0))


fp8_matmul.defvjp(_fp8_matmul_fwd, _fp8_matmul_bwd)

# Forward-only rounding of an operand (the served model's control).
QUANT = {None: lambda x: x, "fp8": fake_fp8, "int8": fake_int8}
MATMUL = {None: lambda a, b: jnp.matmul(a, b, precision=HI),
          "fp8": fp8_matmul, "int8": int8_matmul}
