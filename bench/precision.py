"""Contractions one step of precision below the configuration's: the
controls that the comparison with the reference has to fail.

``dot_bf16x3`` / ``einsum_bf16x3``: a float32 contraction in three
bfloat16 passes (hi·hi + hi·lo + lo·hi), which is what ``precision=HIGH``
computes on a TPU, where the configuration states float32 at HIGHEST.

The rounding uses ``lax.reduce_precision``: XLA may drop a round trip
through a narrower type (``f32 -> bf16 -> f32``) as excess precision, but
never an explicit ``reduce_precision``.  The parts are contracted at
HIGHEST, which is exact for bfloat16-representable values, so the same
arithmetic runs on the CPU and on the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _three(contract, a, b):
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    return (contract(a_lo, b_hi) + contract(a_hi, b_lo)) + contract(a_hi, b_hi)


def dot_bf16x3(a, b):
    hp = jax.lax.Precision.HIGHEST
    return _three(lambda x, y: jnp.matmul(x, y, precision=hp), a, b)


def einsum_bf16x3(spec, a, b):
    hp = jax.lax.Precision.HIGHEST
    return _three(lambda x, y: jnp.einsum(spec, x, y, precision=hp), a, b)
