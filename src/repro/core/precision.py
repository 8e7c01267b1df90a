"""Precision emulation for the mixed-precision CTU study (paper §IV-C).

The schemes differ in WHERE quantization hits, which is the paper's whole
point:

  FULL_FP16 — coordinates, Δ, products, sums all fp16.
  FULL_FP8  — coordinates (p, μ′) quantized to fp8 BEFORE the subtract:
              this "compresses the relative positional information between
              pixels and Gaussians" (fp8 resolution at coordinate ~100 px is
              4-8 px), producing the blocky artifacts of Fig. 7(c).
  MIXED     — the paper's CTU: Δ = p − μ′ computed in FP16 (positional info
              preserved), THEN converted to FP8 for the quadratic unit
              (lines 2-7 of Alg. 1); accumulation in FP16.

Quantization rounds a float32 value onto the fp16 / fp8 (float8_e4m3fn)
grid explicitly: round to nearest even on the mantissa bits, the format's
subnormal spacing below its smallest normal, and its overflow (inf for
fp16; NaN for e4m3fn, which has no inf — the cast's own semantics). The
result equals the `x.astype(fmt).astype(float32)` round trip bit for bit,
but is written in integer and float32 ops only: XLA may drop a
down-then-up convert pair when it fuses (it does under `vmap`), and Mosaic
has no lowering for `lax.reduce_precision`. So the jnp path and the Pallas
kernels quantize identically on every backend, batched or not.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class PrecisionScheme:
    coord: str = "fp32"   # p, μ′, conic entries entering the unit
    delta: str = "fp32"   # Δ after the subtract (input to the quad unit)
    mul: str = "fp32"     # multiplier outputs (lines 2-5)
    acc: str = "fp32"     # adder outputs (lines 6-7)
    # Conservative threshold slack: the CTU tests lhs > E·(1-slack) so the
    # KNOWN bounded quantization error of the quad unit can only produce
    # false positives (wasted work), never false negatives (quality loss).
    # FULL_FP8 cannot be rescued this way: its coordinate quantization error
    # is unbounded in E (several pixels of positional blur).
    slack: float = 0.0

    def q_coord(self, x):
        return quantize(x, self.coord)

    def q_delta(self, x):
        return quantize(x, self.delta)

    def q_mul(self, x):
        return quantize(x, self.mul)

    def q_acc(self, x):
        return quantize(x, self.acc)


FULL_FP32 = PrecisionScheme()
FULL_FP16 = PrecisionScheme("fp16", "fp16", "fp16", "fp16")
# fp8 multiplier INPUTS, fp16 products/accumulation (fp8 x fp8 products are
# exact in fp16) — the standard narrow-multiplier / wide-accumulator MAC.
FULL_FP8 = PrecisionScheme("fp8", "fp8", "fp16", "fp16", slack=0.15)
MIXED = PrecisionScheme("fp16", "fp8", "fp16", "fp16", slack=0.15)


# (mantissa bits, smallest normal exponent, largest finite, overflow value)
_FORMATS = {
    "fp16": (10, -14, 65504.0, jnp.inf),
    "fp8": (3, -6, 448.0, jnp.nan),         # float8_e4m3fn
}


def _round_mantissa(x, mantissa_bits: int):
    """Round float32 `x` to `mantissa_bits` explicit mantissa bits, to
    nearest with ties to even, on its bit pattern (exponent range kept;
    inf/NaN pass through)."""
    shift = 23 - mantissa_bits
    bits = lax.bitcast_convert_type(x, jnp.int32)
    odd = (bits >> shift) & 1
    rounded = (bits + ((1 << (shift - 1)) - 1) + odd) & ~((1 << shift) - 1)
    finite = (bits & 0x7F800000) != 0x7F800000
    return jnp.where(finite, lax.bitcast_convert_type(rounded, jnp.float32),
                     x)


def quantize(x, kind: str):
    """float32 `x` rounded onto the `kind` grid ("fp32" | "fp16" | "fp8"),
    returned as float32 — bit-identical to the cast round trip."""
    if kind == "fp32":
        return x
    if kind not in _FORMATS:
        raise ValueError(kind)
    mbits, emin, fmax, overflow = _FORMATS[kind]
    ulp_sub = 2.0 ** (emin - mbits)          # subnormal spacing (exact)
    y = jnp.where(jnp.abs(x) < 2.0 ** emin,
                  jnp.round(x * (1.0 / ulp_sub)) * ulp_sub,
                  _round_mantissa(x, mbits))
    return jnp.where(jnp.abs(y) > fmax, jnp.where(x < 0, -overflow, overflow),
                     y)
