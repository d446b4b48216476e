"""int8 matmul with the per-channel scale folded into the epilogue (the
round-7 int8 decode lever).

The round-5 int8 path materialized a full bf16 weight per layer before
each matmul (``dequant_tree`` -> ``(q * s).astype(bf16)`` -> ``x @ w``):
HBM sees the int8 read AND the bf16 write+read of the materialized
weight, which is why int8 decode sat at 0.65 of sustained bandwidth
while reading half the bytes of bf16. The fix is to never materialize:

    y = (x @ q) * s            # q int8 streams straight into the dot,
                               # one f32 multiply per OUTPUT element

which is exact per output channel — scaling a column after the
K-reduction is algebraically identical to scaling the column's weights
before it; the only difference from the materialize path is floating-
point accumulation order (the same contract as ops.nf4_kernel).

Two execution paths, selected per shape:

  * Pallas kernel (TPU decode shapes): streams the int8 tile from HBM,
    widens to the activation dtype in VMEM (|q| <= 127 is exact in
    bf16), feeds the MXU, applies the scale row to the f32 accumulator
    before writeback. Grid = (N tiles, K stripes) of ONE launch with an
    f32 accumulator across the K axis; `_tiles` picks the stripe so the
    program's own VMEM estimate fits, and a K that fits whole is one
    stripe — the same aggregated-launch layout as ops.nf4_kernel.
  * XLA mixed-dtype dot (everything else, and all of CPU CI):
    ``lax.dot_general`` takes an int8 rhs with f32 accumulation
    directly, so even the fallback never materializes a scaled weight.

`int8_dot` is dispatched from models.transformer._dot when
models.quant.int8_fold_enabled() leaves 2-D QuantizedTensor leaves
packed (default ON; INT8_FOLD=0 restores dequant-materialize). Token
parity with the materialize path is pinned by tests/test_int8_kernel.py
and the serving parity suites.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.quant import QuantizedTensor

TILE_N = 128

# What `_tiles` plans a program's VMEM footprint against, and what Mosaic
# is allowed to use (`vmem_limit_bytes`): the difference is room for the
# compiler's own temporaries, which the estimate cannot see.
VMEM_BUDGET = 12 * 1024 * 1024
VMEM_LIMIT = 32 * 1024 * 1024

# Tests flip this to run the kernel through the Pallas interpreter on the
# CPU backend (slow, exact semantics) — the kernel itself targets TPU.
_INTERPRET = False

# Trace-time dispatch counter: incremented once per kernel-path call SITE
# per trace (under lax.scan the body traces once for all layers), so
# tests can pin "launch sites per decode step" without running on-chip.
_launches = 0

# Trace-time record of where each matmul site ran: (m, k, n) -> "pallas
# tn=..,tk=.." or "xla". chip_smoke.py prints it, so a backend string
# that stops matching cannot turn the kernel off unnoticed.
_sites: Dict[Tuple[int, int, int], str] = {}


def _vmem_bytes(m: int, tk: int, tn: int, x_bytes: int) -> int:
    """Per-program VMEM footprint estimate for one (tk, tn) grid step.
    Pipelined blocks are double-buffered: the x block [m, tk], the int8
    weight tile [tk, tn], the (sublane-padded) scale row [8, tn] f32 and
    the out tile [m, tn]. In-kernel temporaries exist once: the int32
    widening of the tile, its activation-dtype copy, the f32 accumulator
    scratch and the f32 partial product."""
    pipelined = (m * tk * x_bytes + tk * tn + 8 * tn * 4
                 + m * tn * x_bytes)
    temps = tk * tn * 4 + tk * tn * x_bytes + 2 * m * tn * 4
    return 2 * pipelined + temps


def _tiles(n: int, k: int, m: int, x_bytes: int) -> Optional[Tuple[int, int]]:
    """(tn, tk) moving the most weight bytes per grid step among the
    tiles whose own estimate fits VMEM_BUDGET (ties: the longer K stripe,
    so a shape that fits whole runs one K step). tn divides N, tk divides
    K in multiples of 128. None when even 128 x 128 does not fit (a very
    large m): the shape then takes the XLA path."""
    best = None
    units = k // 128
    for tn in (512, 256, TILE_N):
        if n % tn:
            continue
        for d in range(units, 0, -1):
            if units % d:
                continue
            tk = 128 * d
            if _vmem_bytes(m, tk, tn, x_bytes) <= VMEM_BUDGET:
                cand = (tk * tn, tk, tn)
                if best is None or cand > best:
                    best = cand
                break
    return None if best is None else (best[2], best[1])


@functools.lru_cache(maxsize=64)
def _make_kernel(m: int, k: int, n: int, out_dtype: str,
                 interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tn, tk = _tiles(n, k, m, jnp.dtype(out_dtype).itemsize)

    def kernel(x_ref, q_ref, s_ref, out_ref, acc_ref):
        kk = pl.program_id(1)

        @pl.when(kk == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # int32 FIRST (Mosaic has no vector i8->float cast), then the
        # activation dtype: +-127 is exact in bf16, so the MXU sees the
        # true int8 values at bf16 feed rate.
        w = q_ref[...].astype(jnp.int32).astype(x_ref.dtype)
        acc_ref[...] += jnp.dot(x_ref[...], w,
                                preferred_element_type=jnp.float32)

        @pl.when(kk == pl.num_programs(1) - 1)
        def _():
            # Scale epilogue: one f32 row [1, tn] broadcast over the m
            # rows of the accumulator — per OUTPUT element, not per
            # weight.
            out_ref[...] = (acc_ref[...] * s_ref[...]).astype(out_ref.dtype)

    @jax.jit
    def int8_matmul(x, q, s):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(out_dtype)),
            grid=(n // tn, k // tk),
            in_specs=[
                pl.BlockSpec((m, tk), lambda j, kk: (0, kk)),
                pl.BlockSpec((tk, tn), lambda j, kk: (kk, j)),
                pl.BlockSpec((1, tn), lambda j, kk: (0, j)),
            ],
            out_specs=pl.BlockSpec((m, tn), lambda j, kk: (0, j)),
            scratch_shapes=[pltpu.VMEM((m, tn), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
            name="int8_matmul",
        )(x, q, s)

    return int8_matmul


def _supported(m: int, w: QuantizedTensor, x_bytes: int) -> bool:
    k, n = w.q.shape[-2], w.q.shape[-1]
    assert m % 8 == 0, "caller pads rows to a multiple of 8"
    return (w.q.ndim == 2                 # one layer's weight, not a stack
            and k % 128 == 0              # x lane dim / q sublane tiling
            and n % TILE_N == 0
            and (jax.default_backend() == "tpu" or _INTERPRET)
            and _tiles(n, k, m, x_bytes) is not None)


def int8_dot(x: jnp.ndarray, w: QuantizedTensor) -> jnp.ndarray:
    """x [..., K] @ int8 weight [K, N] (scale folded into the epilogue)
    -> [..., N] in x.dtype.

    Pallas kernel when the shape qualifies (see `_supported`); XLA
    mixed-dtype dot_general otherwise — BOTH stream the int8 bytes and
    scale the accumulator, so enabling the fold never changes which
    shapes serve and never materializes a scaled weight."""
    global _launches
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    m_pad = -(-max(m, 8) // 8) * 8
    n = w.q.shape[-1]
    if _supported(m_pad, w, x.dtype.itemsize):
        _launches += 1
        _sites[(m_pad, k, n)] = "pallas tn=%d,tk=%d" % _tiles(
            n, k, m_pad, x.dtype.itemsize)
        if m_pad != m:
            x2 = jnp.pad(x2, ((0, m_pad - m), (0, 0)))
        fn = _make_kernel(m_pad, k, n, str(x.dtype), interpret=_INTERPRET)
        out = fn(x2, w.q, w.s.astype(jnp.float32))
        return out[:m].reshape(*lead, -1)
    _sites[(m_pad, k, n)] = "xla"
    acc = jax.lax.dot_general(
        x2, w.q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * w.s).astype(x.dtype).reshape(*lead, -1)
