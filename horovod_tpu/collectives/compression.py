"""Gradient compression (``hvd.Compression`` parity).

Reference: ``horovod/torch/compression.py`` -- ``Compression.none`` and
``Compression.fp16`` cast the tensor down before the allreduce and back up
after.  On TPU the natural low-precision wire format is bfloat16 (same
exponent range as fp32 -- no loss scaling needed, and the MXU/ICI path is
optimized for it), so ``bf16`` is provided alongside ``fp16``; both halve
bytes-on-the-wire for fp32 gradients.

``fp8`` (e4m3 + per-bucket scale factors) quarters the wire bytes of fp32
gradients.  Unlike the cast codecs it cannot ride a plain ``psum`` (XLA
reduces in the wire dtype: 3 mantissa bits of ACCUMULATION error and
overflow at ~448), so the collective layer swaps the exchange itself:
``ops.fp8_allreduce`` (alltoall shards -> f32 local reduce -> async-capable
all_gather) for Sum/Average, and per-exchange quantization of the VHDD
``ppermute`` payloads for Adasum -- all arithmetic stays f32 on-chip, fp8
touches only the wire.  Scales ride as one f32 scalar per shard
(negligible).  Quantization noise is ~2^-4 relative per direction (e4m3
rounding); parity tests bound it.

The casts/quantizations are emitted inside the traced step, so XLA fuses
them with the fusion-buffer pack and the collective kernel -- the
"compression" costs no extra HBM round trip.
"""

from __future__ import annotations

import math
import re
from typing import Tuple

import jax.numpy as jnp

E4M3_MAX = 448.0
_SCALE_FLOOR = 1e-30


def fp8_quantize(x, axis=None):
    """Quantize to e4m3 with a max-abs scale (per tensor, or per row of
    ``axis=1``-style leading dim when ``axis`` is given).

    Returns ``(q, scale)``: ``x ~= q.astype(f32) * scale``.
    """
    x32 = x.astype(jnp.float32)
    # ``initial=0.0`` guards degenerate reductions: a zero-size axis has
    # nothing to reduce over (jnp.max would raise), and an all-zero row
    # must land on absmax == 0, not garbage.
    if axis is None:
        absmax = jnp.max(jnp.abs(x32), initial=0.0)
    else:
        red = tuple(i for i in range(x32.ndim) if i != axis)
        absmax = jnp.max(jnp.abs(x32), axis=red, keepdims=False, initial=0.0)
    # All-zero (or empty) rows use scale 1.0 so quantize and dequantize
    # both produce EXACT zeros; _SCALE_FLOOR only backstops nonzero rows
    # whose absmax underflows the division.
    scale = jnp.where(absmax > 0.0,
                      jnp.maximum(absmax / E4M3_MAX, _SCALE_FLOOR),
                      jnp.ones_like(absmax))
    if axis is None:
        q = (x32 / scale).astype(jnp.float8_e4m3fn)
    else:
        shape = [1] * x32.ndim
        shape[axis] = -1
        q = (x32 / scale.reshape(shape)).astype(jnp.float8_e4m3fn)
    return q, scale


def fp8_dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


class Compressor:
    """Compress/decompress around a collective."""

    @staticmethod
    def compress(tensor):
        """Return (compressed_tensor, context_for_decompress)."""
        raise NotImplementedError

    @staticmethod
    def decompress(tensor, ctx):
        raise NotImplementedError


class NoneCompressor(Compressor):
    @staticmethod
    def compress(tensor):
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor


class _CastCompressor(Compressor):
    wire_dtype: jnp.dtype = None  # set by subclasses

    @classmethod
    def compress(cls, tensor):
        dtype = tensor.dtype
        if jnp.issubdtype(dtype, jnp.floating) and \
                jnp.dtype(dtype).itemsize > jnp.dtype(cls.wire_dtype).itemsize:
            return tensor.astype(cls.wire_dtype), dtype
        return tensor, None

    @classmethod
    def decompress(cls, tensor, ctx):
        return tensor if ctx is None else tensor.astype(ctx)


class FP16Compressor(_CastCompressor):
    wire_dtype = jnp.float16


class BF16Compressor(_CastCompressor):
    wire_dtype = jnp.bfloat16


def is_elementwise(compression) -> bool:
    """True for the codecs that act on every element alone (none and the
    casts): compressing a bucket's leaves one by one gives the same bits
    as compressing the bucket, so the exchange needs no flat buffer."""
    return compression is None or compression is NoneCompressor or (
        isinstance(compression, type)
        and issubclass(compression, _CastCompressor))


class FP8Compressor(Compressor):
    """e4m3 wire with per-bucket scales -- an EXCHANGE-level codec.

    ``compress``/``decompress`` are identities: fp8 cannot ride a plain
    psum (see module docstring), so the collective layer recognises
    ``wire_format == "fp8_e4m3"`` and swaps the exchange itself
    (``ops.fp8_allreduce`` for Sum/Average; quantized VHDD permutes for
    Adasum).  Surfaces that cannot swap the exchange raise rather than
    silently sum in fp8.
    """
    wire_format = "fp8_e4m3"

    @staticmethod
    def compress(tensor):
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor


def is_fp8(compression) -> bool:
    return getattr(compression, "wire_format", "").startswith("fp8")


class _ErrorFeedbackCompressor(Compressor):
    """Base for the error-feedback EXCHANGE-level codecs (PowerSGD / top-k).

    Like :class:`FP8Compressor`, ``compress``/``decompress`` are identities:
    the codec cannot ride a plain psum, so the collective layer recognises
    ``wire_format`` and swaps the exchange (``ops.powersgd_allreduce`` /
    ``ops.topk_allreduce``).  Unlike fp8, the exchange is LOSSY in a way that
    biases training unless the per-rank compression error is fed back into
    the next step's gradient -- ``DistributedOptimizer`` threads that
    residual through the optimizer state (see ``optim/distributed.py``).
    """
    wire_format = ""

    @staticmethod
    def compress(tensor):
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor


def is_powersgd(compression) -> bool:
    return getattr(compression, "wire_format", "") == "powersgd"


def is_topk(compression) -> bool:
    return getattr(compression, "wire_format", "") == "topk"


class _HierLegCompressor(Compressor):
    """Per-leg EXCHANGE-level codec for the two-level (DCN x ICI) path.

    Carries one codec per hop: ``ici`` rides the fast intra-slice legs
    (reduce-scatter + allgather), ``dcn`` only the slow cross-slice hop.
    ``compress``/``decompress`` are identities -- like fp8, the collective
    layer recognises ``wire_format == "hier_legs"`` and swaps the exchange
    for ``ops.hierarchical_allreduce`` with the legs' codecs applied
    inside.  The ICI leg must stay psum-compatible (none/fp16/bf16); the
    DCN leg may additionally be fp8 or an error-feedback codec
    (powersgd/topk), whose residual then lives in the DCN-shard domain.
    """
    wire_format = "hier_legs"
    ici = NoneCompressor
    dcn = NoneCompressor

    @staticmethod
    def compress(tensor):
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor


def is_hier_legs(compression) -> bool:
    return getattr(compression, "wire_format", "") == "hier_legs"


def hier_leg_compressor(ici, dcn):
    """Memoized per-leg codec class (see :class:`_HierLegCompressor`).

    Registered on :class:`Compression` under its ``__name__`` like the
    parameterized codecs, so join replay resolves it by name.
    """
    ici = parse_compression(ici)
    dcn = parse_compression(dcn)
    if is_hier_legs(ici) or is_hier_legs(dcn):
        raise ValueError("per-leg codecs do not nest")
    if getattr(ici, "wire_format", ""):
        raise ValueError(
            f"ICI leg codec must be psum-compatible (none|fp16|bf16), "
            f"got {ici.__name__}")
    name = f"Hier{ici.__name__}Dcn{dcn.__name__}"
    cls = getattr(Compression, name, None)
    if cls is None:
        cls = type(name, (_HierLegCompressor,), {"ici": ici, "dcn": dcn})
        setattr(Compression, name, cls)
    return cls


def is_error_feedback(compression) -> bool:
    """True for codecs whose exchange needs error-feedback residual state.
    A per-leg codec is error-feedback iff its DCN leg is."""
    if is_hier_legs(compression):
        return is_error_feedback(compression.dcn)
    return is_powersgd(compression) or is_topk(compression)


def _fraction_token(fraction: float) -> str:
    # "0.01" -> "0p01", "1e-05" -> "1em05": keeps the class name a valid
    # identifier while staying invertible for join replay on drained ranks.
    return ("%g" % fraction).replace(".", "p").replace("-", "m")


def _parse_fraction_token(token: str) -> float:
    return float(token.replace("p", ".").replace("m", "-"))


def powersgd_compressor(rank: int):
    """Memoized rank-``r`` PowerSGD codec class (Vogels et al., 2019).

    The class is registered as an attribute of :class:`Compression` under
    its ``__name__`` so the join-replay codec lookup (``joinop._replay``)
    resolves it by name like the builtin codecs.
    """
    rank = int(rank)
    if rank < 1:
        raise ValueError(f"powersgd rank must be >= 1, got {rank}")
    name = f"PowerSGD{rank}Compressor"
    cls = getattr(Compression, name, None)
    if cls is None:
        cls = type(name, (_ErrorFeedbackCompressor,),
                   {"wire_format": "powersgd", "rank": rank})
        setattr(Compression, name, cls)
    return cls


def topk_compressor(fraction: float):
    """Memoized top-``fraction`` magnitude-sparsification codec (DGC-style,
    Lin et al., 2018).  Registered on :class:`Compression` like
    :func:`powersgd_compressor`."""
    fraction = float(fraction)
    if not 0.0 < fraction <= 1.0:
        raise ValueError(
            f"topk fraction must be in (0, 1], got {fraction}")
    name = f"TopK{_fraction_token(fraction)}Compressor"
    cls = getattr(Compression, name, None)
    if cls is None:
        cls = type(name, (_ErrorFeedbackCompressor,),
                   {"wire_format": "topk", "fraction": fraction})
        setattr(Compression, name, cls)
    return cls


def resolve_compressor_name(name: str):
    """Codec class from its ``__name__`` -- the join-replay lookup.

    Builtin and already-instantiated parameterized codecs come straight off
    the :class:`Compression` namespace; a parameterized name that was never
    constructed in THIS process (a drained rank replaying a peer's deferred
    op) is re-derived from the encoded parameters.
    """
    for c in vars(Compression).values():
        if isinstance(c, type) and c.__name__ == name:
            return c
    m = re.fullmatch(r"PowerSGD(\d+)Compressor", name)
    if m:
        return powersgd_compressor(int(m.group(1)))
    m = re.fullmatch(r"TopK(.+)Compressor", name)
    if m:
        return topk_compressor(_parse_fraction_token(m.group(1)))
    m = re.fullmatch(r"Hier(.+?)Dcn(.+)", name)
    if m:
        return hier_leg_compressor(resolve_compressor_name(m.group(1)),
                                   resolve_compressor_name(m.group(2)))
    raise KeyError(f"unknown compressor {name!r}")


def parse_compression(spec):
    """``HOROVOD_COMPRESSION`` spec -> codec class.

    Accepts ``none``/``fp16``/``bf16``/``fp8``, ``powersgd:<rank>`` and
    ``topk:<fraction>``; a codec class passes through unchanged.  A
    per-leg spec names a codec per hop of the two-level exchange, e.g.
    ``ici:none,dcn:fp8`` (omitted legs default to ``none``).
    """
    if spec is None:
        return Compression.none
    if isinstance(spec, type):
        return spec
    s = str(spec).strip().lower()
    if "ici:" in s or "dcn:" in s:
        legs = {}
        for part in s.split(","):
            leg, sep, sub = part.strip().partition(":")
            if leg not in ("ici", "dcn") or not sep:
                raise ValueError(
                    f"bad per-leg compression spec {spec!r}: expected "
                    f"comma-separated ici:<codec>,dcn:<codec> entries")
            if leg in legs:
                raise ValueError(
                    f"bad per-leg compression spec {spec!r}: duplicate "
                    f"{leg} leg")
            legs[leg] = sub
        return hier_leg_compressor(legs.get("ici", "none"),
                                   legs.get("dcn", "none"))
    plain = {"none": Compression.none, "fp16": Compression.fp16,
             "bf16": Compression.bf16, "fp8": Compression.fp8}
    if s in plain:
        return plain[s]
    kind, sep, arg = s.partition(":")
    if sep:
        try:
            if kind == "powersgd":
                return powersgd_compressor(int(arg))
            if kind == "topk":
                return topk_compressor(float(arg))
        except ValueError as e:
            raise ValueError(f"bad compression spec {spec!r}: {e}") from None
    raise ValueError(
        f"bad compression spec {spec!r}: expected none|fp16|bf16|fp8|"
        f"powersgd:<rank>|topk:<fraction>|ici:<codec>,dcn:<codec>")


def powersgd_matrix_shape(size: int) -> Tuple[int, int]:
    """Near-square matricization of a flat bucket: ``m = ceil(sqrt(size))``
    rows, ``c = ceil(size / m)`` cols (zero-padded to ``m * c``).  Shared by
    the exchange, the wire accounting, and the join-replay width check."""
    size = int(size)
    if size < 1:
        raise ValueError(f"bucket size must be >= 1, got {size}")
    m = int(math.ceil(math.sqrt(size)))
    c = int(math.ceil(size / m))
    return m, c


def powersgd_effective_rank(size: int, rank: int) -> int:
    m, c = powersgd_matrix_shape(size)
    return max(1, min(int(rank), m, c))


def powersgd_factor_widths(size: int, rank: int) -> Tuple[int, int]:
    """Flat widths of the (P, Q) factors a rank-``rank`` exchange puts on
    the wire for a ``size``-element bucket: ``(r_eff * m, r_eff * c)``."""
    m, c = powersgd_matrix_shape(size)
    r = max(1, min(int(rank), m, c))
    return r * m, r * c


def topk_count(size: int, fraction: float) -> int:
    """Number of (value, index) pairs a top-``fraction`` exchange keeps."""
    return max(1, int(math.ceil(int(size) * float(fraction))))


def wire_payload_bytes(compression, size: int,
                       itemsize: int = 4, world: int = 1) -> int:
    """Estimated allreduce-equivalent on-wire payload for one exchange of a
    ``size``-element bucket (used by the ``compression_ratio`` timeline
    counter and the step report's wire accounting; link-bytes scaling by
    ``(n-1)/n`` cancels in ratios so it is left out).

    - dtype codecs: the full bucket at the wire itemsize;
    - fp8: one byte per element (per-shard scales are negligible);
    - powersgd: the P and Q factor allreduces -- ``r*m + r*c`` f32
      elements total;
    - topk: ``k`` f32 values + ``k`` int32 indices allgathered -- an
      allgather moves half the link bytes of an allreduce of the same
      payload, so it counts at half weight.
    """
    size = int(size)
    if size < 1:
        return 0
    if is_hier_legs(compression):
        # ``world`` carries the ICI extent here: the RS/AG legs move the
        # full bucket at the ICI codec's wire width, the DCN hop only a
        # 1/n_ici shard at the DCN codec's width.
        n_ici = max(int(world), 1)
        shard = max(1, (size + n_ici - 1) // n_ici)
        return (wire_payload_bytes(compression.ici, size, itemsize)
                + wire_payload_bytes(compression.dcn, shard, itemsize))
    if is_powersgd(compression):
        pw, qw = powersgd_factor_widths(size, compression.rank)
        return 4 * (pw + qw)
    if is_topk(compression):
        k = topk_count(size, compression.fraction)
        return 8 * k // 2
    if is_fp8(compression):
        return size
    wire_itemsize = itemsize
    wd = getattr(compression, "wire_dtype", None)
    if wd is not None:
        wire_itemsize = min(itemsize, jnp.dtype(wd).itemsize)
    return size * wire_itemsize


class Compression:
    """Namespace matching ``hvd.Compression.{none,fp16}`` plus TPU ``bf16``,
    ``fp8`` (e4m3, per-bucket scales), and the error-feedback exchange
    codecs ``powersgd(rank)`` / ``topk(fraction)`` (parameterized factories;
    instantiated classes are registered here by name for join replay)."""
    none = NoneCompressor
    fp16 = FP16Compressor
    bf16 = BF16Compressor
    fp8 = FP8Compressor
    powersgd = staticmethod(powersgd_compressor)
    topk = staticmethod(topk_compressor)
    hier = staticmethod(hier_leg_compressor)
