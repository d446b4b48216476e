"""int8 matmul with the per-channel scale folded into the epilogue.

A weight-only int8 leaf is ``q`` int8 ``[K, N]`` and one f32 scale per
output channel. Decode reads every weight once a token, so the matmul
is bound by the bytes it moves, and the int8 bytes must be ALL it moves:

    y = (x @ q) * s            # q int8 streams straight into the dot,
                               # one f32 multiply per OUTPUT element

which is exact per output channel — scaling a column after the
K-reduction is algebraically identical to scaling the column's weights
before it; the only difference from dequant-materialize (``x @ (q * s)``,
``INT8_FOLD=0``) is floating-point accumulation order (the same contract
as ops.nf4_kernel). Two things would move more bytes than that, and
both have been measured on the v5e at qwen2-7b widths:

  * materializing a bf16 weight per layer before the matmul (the int8
    read, then a bf16 write and read): never done here;
  * handing the kernel ONE layer's slice of a stacked ``[L, K, N]``
    weight. A Pallas call is a custom call, which needs its operand in
    a buffer of its own, so XLA writes the slice out first: a
    ``dynamic-slice`` that produces ``s8[K, N]``, read + write + the
    kernel's read = three times the traffic. In the served qwen2-7b
    burst tick those copies took 2.39 s of device time against 1.09 s
    for the two large kernels they fed, 48% of the tick (ledger, PR 29).
    So the stacked form below takes the WHOLE stack and the layer
    index: its DMA starts at the layer's offset, and no ``s8`` tensor
    is ever produced (PERF.md, PR 30).

Two execution paths, selected per shape:

  * Pallas kernel (TPU decode and prefill shapes): streams the int8
    tile from HBM, widens to the activation dtype in VMEM (|q| <= 127
    is exact in bf16), feeds the MXU, applies the scale row to the f32
    accumulator before writeback. Grid = (N tiles, K stripes) of ONE
    launch with an f32 accumulator across the K axis; `_tiles` picks the
    stripe so the program's own VMEM estimate fits, and a K that fits
    whole is one stripe — the same aggregated-launch layout as
    ops.nf4_kernel. Given a `QuantizedLayerView` (a stack and a layer
    index, made by runtime.batching's layer scans) the layer index is a
    scalar-prefetch operand and the weight's and scale's block index
    maps lead with it; body, tiles, grid and order are the 2-D form's,
    so the result is bit for bit the 2-D kernel's on the slice.
  * XLA mixed-dtype dot (everything else, and all of CPU CI):
    ``lax.dot_general`` takes an int8 rhs with f32 accumulation
    directly, so even the fallback never materializes a scaled weight.
    A view is sliced first (``dynamic_index_in_dim``): the program a
    scan over the stack runs.

`int8_dot` is dispatched from models.transformer._dot when
models.quant.int8_fold_enabled() leaves 2-D QuantizedTensor leaves (and
views) packed (default ON; INT8_FOLD=0 restores dequant-materialize).
Token parity with the materialize path is pinned by
tests/test_int8_kernel.py, tests/test_burst.py and the serving parity
suites; `_sites` records per shape which path ran ("pallas stacked ..",
"pallas ..", "xla"), and a quantized server prints it (`KERNELS`).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from ..models.quant import QuantizedLayerView, QuantizedTensor

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
                 interpret: bool = False, layers: int = 0):
    """The jitted ``int8_matmul`` of one site. ``layers == 0``: ``(x, q
    [K, N], s [1, N])``. ``layers == L``: ``(layer int32[1], x, q [L, K,
    N], s [L, 1, N])`` — the same body, grid and tiles in the same order,
    with the weight's and the scale's DMA starting at the prefetched
    layer's offset in the stack: bit for bit the 2-D result on the slice,
    and the slice is never written out."""
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

    call = dict(
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(out_dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="int8_matmul",
    )
    grid = (n // tn, k // tk)
    scratch = [pltpu.VMEM((m, tn), jnp.float32)]

    if layers:
        # Index maps take the prefetched scalar after the grid indices; the
        # squeezed leading block dimension hands the body its 2-D tiles.
        def stacked_kernel(layer_ref, *refs):
            kernel(*refs)

        @jax.jit
        def int8_matmul(layer, x, q, s):
            return pl.pallas_call(
                stacked_kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=grid,
                    in_specs=[
                        pl.BlockSpec((m, tk), lambda j, kk, l: (0, kk)),
                        pl.BlockSpec((None, tk, tn),
                                     lambda j, kk, l: (l[0], kk, j)),
                        pl.BlockSpec((None, 1, tn),
                                     lambda j, kk, l: (l[0], 0, j)),
                    ],
                    out_specs=pl.BlockSpec((m, tn), lambda j, kk, l: (0, j)),
                    scratch_shapes=scratch),
                **call,
            )(layer, x, q, s)

        return int8_matmul

    @jax.jit
    def int8_matmul(x, q, s):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((m, tk), lambda j, kk: (0, kk)),
                pl.BlockSpec((tk, tn), lambda j, kk: (kk, j)),
                pl.BlockSpec((1, tn), lambda j, kk: (0, j)),
            ],
            out_specs=pl.BlockSpec((m, tn), lambda j, kk: (0, j)),
            scratch_shapes=scratch,
            **call,
        )(x, q, s)

    return int8_matmul


def _supported(m: int, k: int, n: int, x_bytes: int) -> bool:
    assert m % 8 == 0, "caller pads rows to a multiple of 8"
    return (k % 128 == 0                  # x lane dim / q sublane tiling
            and n % TILE_N == 0
            and (jax.default_backend() == "tpu" or _INTERPRET)
            and _tiles(n, k, m, x_bytes) is not None)


def int8_dot(x: jnp.ndarray,
             w: Union[QuantizedTensor, QuantizedLayerView]) -> jnp.ndarray:
    """x [..., K] @ int8 weight [K, N] (scale folded into the epilogue)
    -> [..., N] in x.dtype. ``w`` is one layer's 2-D leaf, or a
    `QuantizedLayerView` of an ``[L, K, N]`` stack.

    Pallas kernel when the shape qualifies (see `_supported`): on the leaf
    itself, or on the view's WHOLE stack with the layer index prefetched,
    so that no copy of the layer's weight is made for the call. XLA
    mixed-dtype dot_general otherwise (a view is sliced first: the
    program a scan over the stack would have run) — BOTH stream the int8
    bytes and scale the accumulator, so enabling the fold never changes
    which shapes serve and never materializes a scaled weight."""
    global _launches
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    m_pad = -(-max(m, 8) // 8) * 8
    n = w.shape[-1]
    view = isinstance(w, QuantizedLayerView)
    if w.axis != -2:
        raise ValueError("a leaf that rests [out, in] is "
                         "models.transformer._dot_t's, not the kernel's")
    if ((view or w.q.ndim == 2)           # one layer's weight, not a stack
            and _supported(m_pad, k, n, x.dtype.itemsize)):
        _launches += 1
        tn, tk = _tiles(n, k, m_pad, x.dtype.itemsize)
        _sites[(m_pad, k, n)] = (
            f"pallas {'stacked ' if view else ''}tn={tn},tk={tk}")
        if m_pad != m:
            x2 = jnp.pad(x2, ((0, m_pad - m), (0, 0)))
        if view:
            layer = jnp.asarray(w.index, jnp.int32).reshape(1)
            w = w.stack
            args, layers = (layer, x2, w.q), w.q.shape[0]
        else:
            args, layers = (x2, w.q), 0
        fn = _make_kernel(m_pad, k, n, str(x.dtype), interpret=_INTERPRET,
                          layers=layers)
        out = fn(*args, w.s.astype(jnp.float32))
        return out[:m].reshape(*lead, -1)
    if view:
        w = w.layer()
    _sites[(m_pad, k, n)] = "xla"
    acc = jax.lax.dot_general(
        x2, w.q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * w.s).astype(x.dtype).reshape(*lead, -1)
