"""Fused BatchNorm backward: the two-pass Pallas kernels the probe asked
for, plus a flax-compatible ``BatchNorm`` module to dispatch them.

``examples/bn_bwd_probe.py`` attributes ~45 ms of the RN50 backward to
HBM-bound BN/relu/residual chains and establishes the 7N two-pass floor:
the backward of a train-mode BN is two full passes over the activation
arena (pass 1 reads ``x``/``dy`` to reduce the per-channel sums
``dbeta = sum(dy)`` and ``dgamma = sum(dy * xhat)``; pass 2 reads them
again and writes ``dx``), and anything beyond ~5 arena reads + 1 write
is XLA failing to fuse the chain.  The two kernels here are exactly
those passes, gated by ``HOROVOD_PALLAS`` / ``HOROVOD_PALLAS_BN`` and
dispatched from the RN50 model's BN sites via the ``BatchNorm`` module
below (variable collections match ``flax.linen.BatchNorm`` --
``params/{scale,bias}``, ``batch_stats/{mean,var}`` -- and the module
class shares the name, so swapping it in changes neither the param tree
nor checkpoint layout).

Backward closed form (biased batch variance over ``N`` reduce elements,
statistics in f32 like flax):

    dx = scale * rsqrt(var + eps) * (dy - dbeta/N - xhat * dgamma/N)

The XLA reference path computes the identical formula, so the
interpreter-mode parity test pins kernel == reference == autodiff.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas import interpret_mode, pallas_enabled

_MIN_BLOCK = 8


def _row_block(n: int, preferred: int = 512) -> int:
    b = min(preferred, n) // _MIN_BLOCK * _MIN_BLOCK
    while b >= _MIN_BLOCK and n % b:
        b -= _MIN_BLOCK
    return b if b >= _MIN_BLOCK else n


def batch_stats(x):
    """f32 mean/var over every axis but the last (fast variance,
    ``E[x^2] - E[x]^2``, matching flax's default)."""
    xf = x.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(xf, axis=axes)
    var = jnp.maximum(jnp.mean(jnp.square(xf), axis=axes)
                      - jnp.square(mean), 0.0)
    return mean, var


# ---------------------------------------------------------------------------
# Pass 1: per-channel reductions (dbeta, dgamma).
# ---------------------------------------------------------------------------

def _reduce_kernel(x_ref, dy_ref, mean_ref, inv_ref, dbeta_ref, dgamma_ref,
                   sums_scr, *, nblocks):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_scr[...] = jnp.zeros_like(sums_scr)

    dy = dy_ref[...].astype(jnp.float32)
    xhat = ((x_ref[...].astype(jnp.float32) - mean_ref[...])
            * inv_ref[...])
    sums_scr[0:1, :] += jnp.sum(dy, axis=0, keepdims=True)
    sums_scr[1:2, :] += jnp.sum(dy * xhat, axis=0, keepdims=True)

    @pl.when(i == nblocks - 1)
    def _finish():
        dbeta_ref[...] = sums_scr[0:1, :]
        dgamma_ref[...] = sums_scr[1:2, :]


# ---------------------------------------------------------------------------
# Pass 2: dx.
# ---------------------------------------------------------------------------

def _dx_kernel(x_ref, dy_ref, mean_ref, inv_ref, scale_ref, dbeta_ref,
               dgamma_ref, dx_ref, *, inv_n):
    dy = dy_ref[...].astype(jnp.float32)
    xhat = ((x_ref[...].astype(jnp.float32) - mean_ref[...])
            * inv_ref[...])
    dx = (scale_ref[...] * inv_ref[...]
          * (dy - dbeta_ref[...] * inv_n - xhat * dgamma_ref[...] * inv_n))
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _bn_bwd_kernels(x2, dy2, mean, var, scale, eps):
    n, feat = x2.shape
    bn_ = _row_block(n)
    nblocks = n // bn_
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    row = lambda a: a.astype(jnp.float32).reshape(1, feat)
    blk = pl.BlockSpec((bn_, feat), lambda i: (i, 0))
    row_spec = pl.BlockSpec((1, feat), lambda i: (0, 0))
    with jax.named_scope("hvd_bn_bwd_reduce"):
        dbeta, dgamma = pl.pallas_call(
            functools.partial(_reduce_kernel, nblocks=nblocks),
            grid=(nblocks,),
            in_specs=[blk, blk, row_spec, row_spec],
            out_specs=[row_spec, row_spec],
            out_shape=[jax.ShapeDtypeStruct((1, feat), jnp.float32)] * 2,
            scratch_shapes=[pltpu.VMEM((2, feat), jnp.float32)],
            name="hvd_bn_bwd_reduce",
            interpret=interpret_mode(),
        )(x2, dy2, row(mean), row(inv))
    with jax.named_scope("hvd_bn_bwd_dx"):
        dx2 = pl.pallas_call(
            functools.partial(_dx_kernel, inv_n=1.0 / n),
            grid=(nblocks,),
            in_specs=[blk, blk, row_spec, row_spec, row_spec, row_spec,
                      row_spec],
            out_specs=blk,
            out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
            name="hvd_bn_bwd_dx",
            interpret=interpret_mode(),
        )(x2, dy2, row(mean), row(inv), row(scale), dbeta, dgamma)
    return dx2, dgamma[0], dbeta[0]


def fused_bn_backward(x, scale, mean, var, dy, *, eps: float):
    """``(dx, dgamma, dbeta)`` for train-mode BN over the last axis.

    Dispatch: the two-pass Pallas kernels when the ``bn_bwd`` family is
    enabled, the identical XLA closed form otherwise.  ``x``/``dy`` keep
    their dtype on the wire (cast to f32 in-register); ``dgamma``/
    ``dbeta`` come back f32.
    """
    feat = x.shape[-1]
    n = x.size // feat
    x2 = x.reshape(n, feat)
    dy2 = dy.reshape(n, feat)
    if pallas_enabled("bn_bwd"):
        from ..timeline import spans as _spans
        _spans.note_leg("pallas/bn_bwd",
                        nbytes=7 * x.size * x.dtype.itemsize)
        dx2, dgamma, dbeta = _bn_bwd_kernels(x2, dy2, mean, var, scale,
                                             eps)
        return dx2.reshape(x.shape), dgamma, dbeta
    xf = x2.astype(jnp.float32)
    dyf = dy2.astype(jnp.float32)
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    xhat = (xf - mean.astype(jnp.float32)) * inv
    dbeta = jnp.sum(dyf, axis=0)
    dgamma = jnp.sum(dyf * xhat, axis=0)
    dx2 = (scale.astype(jnp.float32) * inv
           * (dyf - dbeta / n - xhat * dgamma / n)).astype(x.dtype)
    return dx2.reshape(x.shape), dgamma, dbeta


# ---------------------------------------------------------------------------
# Train-mode normalize with the fused backward.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def bn_train(x, scale, bias, eps):
    """``(x - mean) * rsqrt(var + eps) * scale + bias`` with batch
    statistics -- forward stays in XLA (it fuses fine), backward routes
    through ``fused_bn_backward``."""
    y, _ = _bn_train_fwd(x, scale, bias, eps)
    return y


def _bn_train_fwd(x, scale, bias, eps):
    mean, var = batch_stats(x)
    inv = jax.lax.rsqrt(var + eps)
    xf = x.astype(jnp.float32)
    y = ((xf - mean) * inv * scale.astype(jnp.float32)
         + bias.astype(jnp.float32))
    return y.astype(x.dtype), (x, scale, mean, var)


def _bn_train_bwd(eps, res, dy):
    x, scale, mean, var = res
    dx, dgamma, dbeta = fused_bn_backward(x, scale, mean, var, dy,
                                          eps=eps)
    return dx, dgamma.astype(scale.dtype), dbeta.astype(scale.dtype)


bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


class BatchNorm(nn.Module):
    """Drop-in subset of ``flax.linen.BatchNorm`` (feature axis -1,
    scale+bias always on) whose train-mode backward runs the fused
    Pallas kernels.  Same class name, param names, and batch_stats
    layout as the flax module, so ``models.resnet`` can swap between
    the two without touching checkpoints."""
    use_running_average: Optional[bool] = None
    momentum: float = 0.99
    epsilon: float = 1e-5
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    scale_init: Callable = nn.initializers.ones
    bias_init: Callable = nn.initializers.zeros

    @nn.compact
    def __call__(self, x, use_running_average: Optional[bool] = None):
        use_ra = nn.merge_param("use_running_average",
                                self.use_running_average,
                                use_running_average)
        feat = x.shape[-1]
        scale = self.param("scale", self.scale_init, (feat,),
                           self.param_dtype)
        bias = self.param("bias", self.bias_init, (feat,),
                          self.param_dtype)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((feat,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((feat,), jnp.float32))
        dtype = self.dtype or x.dtype
        if use_ra:
            inv = jax.lax.rsqrt(ra_var.value + self.epsilon)
            y = ((x.astype(jnp.float32) - ra_mean.value) * inv
                 * scale.astype(jnp.float32) + bias.astype(jnp.float32))
            return y.astype(dtype)
        y = bn_train(x, scale, bias, float(self.epsilon))
        if not self.is_initializing():
            # Running-stat update mirrors flax (f32 EMA; gradients never
            # flow into variables, so recomputing the stats in XLA is
            # side-effect bookkeeping, not a second backward pass).
            mean, var = batch_stats(x)
            m = self.momentum
            ra_mean.value = m * ra_mean.value + (1.0 - m) * mean
            ra_var.value = m * ra_var.value + (1.0 - m) * var
        return y.astype(dtype)


def use_pallas_bn() -> bool:
    """Model-construction-time dispatch for the RN50 BN sites."""
    return pallas_enabled("bn_bwd")
