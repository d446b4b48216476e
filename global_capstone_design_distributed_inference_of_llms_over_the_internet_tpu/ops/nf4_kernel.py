"""Fused NF4 dequant-matmul Pallas kernel (the round-5 nf4 throughput
lever).

XLA cannot fuse the 4-bit unpack + 16-level codebook lookup into the MXU
operand feed: the dequantized weight materializes through a ~20-op VPU
elementwise chain per weight per step (docs/PERFORMANCE.md "Quantized
serving"; its speed on today's machine is not measured). This
kernel streams the PACKED nibbles (0.5 B/weight) + per-block scales from
HBM, dequantizes per N-tile in VMEM, and feeds the MXU directly.

Layout trick: a packed byte holds K-rows (2r, 2r+1) — rather than
interleave rows in VMEM (a sublane shuffle Mosaic lowers badly), the
matmul is split by nibble parity:

    y = x_even @ dequant(high_nibbles) + x_odd @ dequant(low_nibbles)

which is exact because matmul contraction is order-free. The activation
is split host-side (x[:, 0::2], x[:, 1::2] — tiny [M, K] tensors).

Grid: (N tiles, K stripes) with an f32 accumulator across the K axis —
`_tiles` picks the pair that moves the most bytes per step among those
whose own VMEM estimate fits, and a K that fits whole is one stripe.
Tile-size gotchas learned on-chip, encoded as guards below: N
must split into whole tiles (a non-dividing grid silently truncates),
scales ride as f32 so the scale block's sublane count stays legal, and
the uint8 block is widened to int32 BEFORE shifting (Mosaic cannot
legalize vector i8 shrui).

Launch aggregation (round 7): ONE pallas_call already covers all N
tiles of a weight via the grid, so launches/step = matmul SITES, not
tiles. The round-5 count (~80/step at M=16: 7 sites x 16 layers
untamed by scan site-sharing on the per-step path) was dominated by
quantized trees skipping the engine-side QKV and gate+up fusions —
`models.transformer._concat_out_axis` now concatenates packed NF4 (and
int8) leaves exactly, so a layer runs FOUR launches (wqkv, wo, wgu,
wd), each one `pallas_call` whose grid walks the fused weight's full N
extent, and under `lax.scan` those four SITES serve every layer of the
step. Cross-layer aggregation into a single launch is structurally
impossible — attention and norms sit between the matmuls — so 4 sites
is the floor for this architecture, pinned (with the `_launches`
counter below) by the launch-count guard in tests/test_burst.py.

`nf4_dot` is the dispatch wrapper used by the model's matmul sites when
`NF4_KERNEL=1` (utils env flag): it falls back to dequant-then-matmul
for any shape the kernel does not cover, so enabling the flag can never
change reachability — only speed. Token parity with the dequant path is
pinned by tests/test_nf4_kernel.py.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# NOTE on enablement: the NF4_KERNEL env flag is consumed in
# models.quant.dequant_tree (which decides whether packed NF4 leaves
# reach the matmul sites at all); nf4_dot itself dispatches purely on
# leaf type and shape.
from ..models.quant import NF4_BLOCK, NF4_LEVELS, NF4Tensor, _lut16

TILE_N = 128
# Packed rows per scale row: a packed byte holds two K rows, and one
# absmax scale covers NF4_BLOCK of them.
_ROWS_PER_SCALE = NF4_BLOCK // 2

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


def _vmem_bytes(m: int, tp: int, tn: int, x_bytes: int) -> int:
    """Per-program VMEM footprint estimate for one (tp, tn) grid step
    (tp = PACKED rows, i.e. 2*tp rows of K). Pipelined blocks are
    double-buffered: two x blocks [m, tp], packed [tp, tn] u8, scales
    [tp/32, tn] f32 and the out tile [m, tn]. In-kernel temporaries exist
    once per [tp, tn] element: the int32 widening of the packed bytes,
    one int32 nibble plane, the repeated f32 scale, the f32 codebook
    value and its scaled product, and the two activation-dtype weight
    tiles; plus the f32 accumulator scratch and partial product."""
    pipelined = (2 * m * tp * x_bytes + tp * tn
                 + max(tp // _ROWS_PER_SCALE, 8) * tn * 4
                 + m * tn * x_bytes)
    temps = tp * tn * (5 * 4 + 2 * x_bytes) + 2 * m * tn * 4
    return 2 * pipelined + temps


def _tiles(n: int, k: int, m: int, x_bytes: int) -> Optional[Tuple[int, int]]:
    """(tn, tp) moving the most weight bytes per grid step among the
    tiles whose own estimate fits VMEM_BUDGET (ties: the longer K stripe,
    so a shape that fits whole runs one K step). tn divides N; tp divides
    the packed row count P = K/2 and is either P itself or a multiple of
    256, so the scale block [tp/32, tn] keeps a legal f32 sublane count.
    None when nothing fits: the shape then takes the dequant path."""
    p = k // 2
    stripes = [p] + [t for t in range(p - p % 256, 0, -256)
                     if t != p and p % t == 0]
    best = None
    for tn in (512, 256, TILE_N):
        if n % tn:
            continue
        for tp in stripes:
            if _vmem_bytes(m, tp, tn, x_bytes) <= VMEM_BUDGET:
                cand = (tp * tn, tp, tn)
                if best is None or cand > best:
                    best = cand
                break
    return None if best is None else (best[2], best[1])

# MOSAIC CONSTRAINT on quant._lut16 (one shared select tree): the level
# constants must stay f32 — bf16 levels would make Mosaic relayout the
# int32-derived (8,128) i1 mask tiles into (16,128) bf16 selects, which
# it cannot ('Invalid relayout ... vector<...xi1>'). quant.py documents
# the same requirement from its side.


@functools.lru_cache(maxsize=64)
def _make_kernel(m: int, k: int, n: int, out_dtype: str,
                 interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p = k // 2
    tn, tp = _tiles(n, k, m, jnp.dtype(out_dtype).itemsize)
    ts = tp // _ROWS_PER_SCALE

    def kernel(xe_ref, xo_ref, pk_ref, sc_ref, out_ref, acc_ref):
        kk = pl.program_id(1)

        @pl.when(kk == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        packed = pk_ref[...].astype(jnp.int32)  # int32 FIRST: Mosaic has
        hi = (packed >> 4) & 0xF                # no vector i8 shrui
        lo = packed & 0xF
        scale = jnp.repeat(sc_ref[...], _ROWS_PER_SCALE, axis=0)  # [tp, tn]
        # Weights take the ACTIVATION dtype (bf16 serving feeds the MXU at
        # bf16 rate; an f32 activation keeps f32 — also what the CPU
        # interpreter's dot supports).
        wdt = xe_ref.dtype
        wh = (_lut16(hi, NF4_LEVELS) * scale).astype(wdt)
        wl = (_lut16(lo, NF4_LEVELS) * scale).astype(wdt)
        acc = jnp.dot(xe_ref[...], wh, preferred_element_type=jnp.float32)
        acc_ref[...] += acc + jnp.dot(xo_ref[...], wl,
                                      preferred_element_type=jnp.float32)

        @pl.when(kk == pl.num_programs(1) - 1)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    @jax.jit
    def nf4_matmul(xe, xo, packed, scales):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(out_dtype)),
            grid=(n // tn, p // tp),
            in_specs=[
                pl.BlockSpec((m, tp), lambda j, kk: (0, kk)),
                pl.BlockSpec((m, tp), lambda j, kk: (0, kk)),
                pl.BlockSpec((tp, tn), lambda j, kk: (kk, j)),
                pl.BlockSpec((ts, tn), lambda j, kk: (kk, j)),
            ],
            out_specs=pl.BlockSpec((m, tn), lambda j, kk: (0, j)),
            scratch_shapes=[pltpu.VMEM((m, tn), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
            name="nf4_matmul",
        )(xe, xo, packed, scales)

    return nf4_matmul


def _supported(m: int, w: NF4Tensor, x_bytes: int) -> bool:
    in_dim = w.in_dim
    n = w.packed.shape[-1]
    assert m % 8 == 0, "caller pads rows to a multiple of 8"
    return (w.packed.ndim == 2            # one layer's weight, not a stack
            and in_dim == w.packed.shape[0] * 2   # no in-axis padding
            and in_dim % 128 == 0
            and n % TILE_N == 0
            and (jax.default_backend() == "tpu" or _INTERPRET)
            and _tiles(n, in_dim, m, x_bytes) is not None)


def nf4_dot(x: jnp.ndarray, w: NF4Tensor) -> jnp.ndarray:
    """x [..., K] @ NF4 weight [K, N] -> [..., N] in x.dtype.

    Kernel path when the shape qualifies (see `_supported`); exact
    dequant-then-matmul fallback otherwise — enabling the kernel never
    changes which shapes serve."""
    global _launches
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    m_pad = -(-max(m, 8) // 8) * 8
    n = w.packed.shape[-1]
    if _supported(m_pad, w, x.dtype.itemsize):
        _launches += 1
        _sites[(m_pad, k, n)] = "pallas tn=%d,tk=%d" % tuple(
            t * f for t, f in zip(_tiles(n, k, m_pad, x.dtype.itemsize),
                                  (1, 2)))
        if m_pad != m:
            x2 = jnp.pad(x2, ((0, m_pad - m), (0, 0)))
        fn = _make_kernel(m_pad, k, n, str(x.dtype), interpret=_INTERPRET)
        out = fn(x2[:, 0::2], x2[:, 1::2], w.packed,
                 w.scales.astype(jnp.float32))
        return out[:m].reshape(*lead, -1)
    _sites[(m_pad, k, n)] = "xla"
    return x @ w.dequant().astype(x.dtype)
