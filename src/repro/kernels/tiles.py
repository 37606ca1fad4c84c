"""TPU tile geometry shared by the kernels and the kernel selector.

One source of truth for the hardware granules the Pallas kernels tile
against, so the compile-time selector (``repro.core.selection``) and the
profile-guided autotuner (``repro.autotune``) reason about exactly the
blocks the kernels will use.

Granules are dtype-dependent on TPU: the lane (minor) dim is always 128
wide, but the sublane granule is ``32 / itemsize`` rows (f32 → 8,
bf16 → 16, int8 → 32) because the register file packs narrower elements
deeper.  The VMEM working-set math is parametrized the same way — a
bf16 operand tile holds twice the elements of an f32 tile in the same
bytes, so the K-dim block cap scales up instead of leaving half the
budget idle.
"""

from __future__ import annotations

from typing import List, Tuple

#: MXU/VPU lane width (minor-most dim granule, all dtypes).
LANE = 128
#: Sublane granule for f32 (second-minor dim).  Dtype-aware callers use
#: :func:`sublane_for` instead.
SUBLANE = 8
#: Per-core VMEM the block working set must fit well under (~16 MiB on
#: current TPUs; the budget is the full size — callers compare their
#: resident tiles against it).
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

#: Padded rows up to which :func:`pick_block` streams the weights.
#: Each weight element is read once and used by ``m`` rows, so the
#: kernel moves ``itemsize`` bytes for ``2·m`` operations.  On a TPU v5e
#: (819 GB/s of HBM, 197 TF/s bf16) an f32 matmul at ``highest`` runs
#: six bf16 MXU passes, about 33 TF/s: the weight stream bounds it
#: while ``2·m / 4 · 819e9 < 33e12``, below about 80 rows.  64 is the
#: largest row count under that which every dtype's sublane granule
#: divides; bf16 and int8 stay stream-bound to higher ``m``, so the
#: f32 bound is the one that holds for all three.
STREAM_MAX_ROWS = 64
#: Weight-tile bytes of the streaming geometry.  A grid step has a
#: fixed cost of a fraction of a microsecond; a tile whose DMA takes
#: several microseconds (4 MiB at 819 GB/s: 5.1 µs) hides it, and two
#: such tiles, double-buffered, stay well inside the 16 MiB of VMEM.
STREAM_TILE_BYTES = 4 * 1024 * 1024
#: Widest N block of the streaming geometry.  Every N block re-reads
#: the x rows, so a wide block reads them fewer times; 2048 leaves a
#: 4 MiB f32 tile 512 deep in K.
STREAM_BN = 2048


def ceil_to(n: int, align: int) -> int:
    """Round ``n`` up to a multiple of ``align`` (the one copy of the
    granule-rounding convention)."""
    return -(-n // align) * align


def sublane_for(itemsize: int = 4) -> int:
    """Sublane granule for a dtype of ``itemsize`` bytes: 32/itemsize
    rows (f32 → 8, bf16 → 16, int8 → 32), never below the f32 granule."""
    return max(SUBLANE, 32 // max(1, itemsize))


def block_vmem_bytes(bm: int, bk: int, bn: int, itemsize: int = 4,
                     acc_itemsize: int = 4) -> int:
    """Resident bytes of one fused-matmul block: x(bm,bk) + w(bk,bn)
    tiles in the operand dtype, plus the accumulator and output tiles.

    ``acc_itemsize`` is the accumulator/output element width — 4 for
    the f32 kernels *and* for the int8 kernel (i32 scratch, f32 out);
    it is a parameter rather than a constant so a future f16-out or
    i64-accumulate variant budgets correctly instead of inheriting the
    f32 assumption.
    """
    return itemsize * (bm * bk + bk * bn) + acc_itemsize * 2 * (bm * bn)


def streams_weights(m: int, itemsize: int = 4) -> bool:
    """Whether a matmul of ``m`` rows takes the weight-streaming
    geometry of :func:`pick_block`: its padded row count is at most
    :data:`STREAM_MAX_ROWS`."""
    return ceil_to(m, sublane_for(itemsize)) <= STREAM_MAX_ROWS


def _lane_divisor(total: int, cap: int) -> int:
    """The largest multiple of :data:`LANE` that divides ``total`` (a
    multiple of LANE) and is at most ``cap``; LANE if none is."""
    best = LANE
    for d in range(LANE, min(total, cap) + 1, LANE):
        if total % d == 0:
            best = d
    return best


def pick_block(m: int, k: int, n: int, itemsize: int = 4
               ) -> Tuple[int, int, int]:
    """Block choice for the fused matmul, from its shape alone.

    Few rows (:func:`streams_weights`): the matmul is a stream of weight
    bytes, so one row block covers every row and the weight tile
    ``(bk, bn)`` is as large as :data:`STREAM_TILE_BYTES` allows, with
    ``bk`` and ``bn`` dividing the lane-padded K and N so the weight is
    never padded per call.

    Otherwise the tile geometry: x(bm,bk) + w(bk,bn) + acc/out(bm,bn)
    well under VMEM and MXU-aligned, with a K cap that scales with the
    operand dtype — 512 for f32, 1024 for bf16 — so narrow dtypes
    stream twice the reduction depth through the same VMEM bytes.
    """
    sub = sublane_for(itemsize)
    m_pad, k_pad, n_pad = ceil_to(m, sub), ceil_to(k, LANE), ceil_to(n, LANE)
    if streams_weights(m, itemsize):
        bn = _lane_divisor(n_pad, STREAM_BN)
        bk = _lane_divisor(k_pad, STREAM_TILE_BYTES // (bn * itemsize))
        return m_pad, bk, bn
    bm = min(256, m_pad)
    bn = min(256, n_pad)
    bk = min(512 * 4 // max(1, itemsize), k_pad)
    return bm, bk, bn


def grid_steps(m: int, k: int, n: int, block: Tuple[int, int, int]) -> int:
    """Grid steps of the fused matmul over an (m, k, n) problem padded
    to ``block``: row blocks × column blocks × K chunks."""
    bm, bk, bn = block
    return -(-m // bm) * -(-n // bn) * -(-k // bk)


#: Candidate caps the autotuner sweeps around :func:`pick_block`.  Small
#: on purpose: the grid is multiplied by every (shape, batch) tactic key
#: and each candidate costs a compile + a micro-benchmark.
_BM_CANDIDATES = (64, 128, 256)
_BK_CANDIDATES = (256, 512, 1024)
_BN_CANDIDATES = (128, 256)


def enumerate_blocks(m: int, k: int, n: int, itemsize: int = 4,
                     max_candidates: int = 8) -> List[Tuple[int, int, int]]:
    """Block-geometry candidates for the autotuner: the heuristic's
    :func:`pick_block` choice first (so the prior is always measured),
    then a small cap grid around it, clipped to the padded problem
    dims, deduplicated, and filtered to blocks whose working set fits
    VMEM."""
    sub = sublane_for(itemsize)
    m_cap, k_cap, n_cap = ceil_to(m, sub), ceil_to(k, LANE), ceil_to(n, LANE)
    blocks = [pick_block(m, k, n, itemsize)]
    for bm in _BM_CANDIDATES:
        for bk in _BK_CANDIDATES:
            for bn in _BN_CANDIDATES:
                b = (min(bm, m_cap), min(bk, k_cap), min(bn, n_cap))
                if b in blocks:
                    continue
                if block_vmem_bytes(*b, itemsize=itemsize) > VMEM_BUDGET_BYTES:
                    continue
                blocks.append(b)
    return blocks[:max_candidates]
