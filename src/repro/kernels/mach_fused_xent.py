"""Fused projection + MACH cross-entropy (the logit-free training loss).

``mach_xent.py`` fuses the R-head cross-entropy *given* the logits — but
the trainer still materializes the full (N, R·B) logits tensor in HBM
via the head matmul, so train-time activation memory is O(N·R·B) and
the paper's O(d log K) story holds only for parameters.  This kernel
fuses the hidden→bucket projection into the loss itself.

Both the dense-h and the sparse-h (padded-ELL) families share one
d-blocked forward:

    forward grid (N/bn, C/bc, D/bd), C = R·B columns, d minor.  W
    streams through (bd, bc) VMEM tiles and h through (bn, bd) slices
    (for sparse h the slice is densified in VMEM from ELL cols/vals via
    a one-hot contraction); the logits tile accumulates across d blocks
    in (bn, bc) scratch.  At the last d block the optional bias (1, bc)
    is broadcast-added and the tile is reduced: an online per-head
    max / sum-exp (flash-attention-style, so heads may span several
    column blocks) and a gather-free label pick accumulate into (bn, R)
    scratch.  Neither the (N, R·B) logits tensor nor a full-d operand
    tile ever exists — per-step VMEM is O(bn·bd + bd·bc + bn·bc), so
    LM-scale d (mistral-large d=12288) fits the same budget as d=128.

Column blocks are head-aligned: when B fits the VMEM budget a block
covers ``nh`` whole heads (no online rescaling ever fires — each head's
logsumexp completes in its block); when B is larger than the budget a
block is a bucket-slice of a single head and the online update streams
the head's logsumexp across blocks.  Both cases run the same body.

The custom VJPs recompute each logits tile ONCE (the standard fused-CE
trade) from the saved per-head logsumexp:

    dlogits[n, rB+b] = g_n · (softmax(logits)[n, r, b] − 1[b = y_nr])

Dense h: a single kernel, grid (C/bc, 2·rows/bn·D/bd) with two phases
per column block.  Phase 1 sweeps the d blocks inside each row block,
rebuilding its logits tile once; at the last d block it forms dlogits
into a (rows, bc) VMEM scratch and reduces dbias into the (1, bc)
output row.  Phase 2 sweeps the row blocks inside each d block:
``dW_blk += h_kᵀ @ dlogits`` accumulates in the (bd, bc) output block
over consecutive steps, and ``dh_blk = dlogits @ W_kᵀ`` is written per
column block (the wrapper sums the C/bc partials).  No output block is
revisited after its index moved away: a TPU writes an output block back
when its index changes and never re-reads it.  Batches beyond
``_BWD_ROWS`` rows run either family's backward once per row chunk.
Activation residuals are the inputs and the (N, R) logsumexp — O(N·d)
dense / O(N·J) sparse, independent of R·B.

The per-head reductions work on the 2-D (bn, bc) tile with one lane
mask per head — never a (bn, nh, width) reshape, which splits lanes
when width < 128 (ODP's B = 32) — and read and write the (bn, R)
per-head labels and statistics through masked lane selects, since the
first head of a block is a traced index.  f32 contractions run at f32
precision (``_F32``).

Sparse features (the paper's ODP d=422k workload): the ``*_sparse``
entry points take the batch in padded-ELL form — ``cols/vals (N, J)``,
row n's features at ``cols[n, :]`` with weights ``vals[n, :]`` (padding
carries val 0) — as produced from CSR by ``ops.mach_fused_xent_csr``.
The forward densifies each d block's slice of the activation *in VMEM*
via a one-hot contraction (``A[n, p] = Σ_j vals[n, j]·1[cols[n, j] =
d0+p]``, MXU/Mosaic-friendly, duplicate ids sum like a CSR scatter-add);
the dense (N, d) activation never exists in HBM.  The backward works
from the batch's entries instead of sweeping d per row block: the
wrapper sorts the flat (col, row, val) entries by col (sentinel slots
past the last block) and cuts them into work items, each inside one d
block and one SMEM chunk of entries, every d block holding at least
one.  One kernel walks the items twice per column block: the logits
phase adds ``val · W[col]`` into the entry's row of a (rows, bc) VMEM
scratch, reading each (bd, bc) W block once, then turns the scratch
into dlogits and dbias; the dW phase zeroes each (bd, bc) dW block at
its first item, adds ``val · dlogits[row]`` into the entry's row of
it, and writes it once.  Both are dynamic row loads and row adds in
f32 on the VPU; duplicate ids sum.  A DMA of a single W row is not an
option: W's HBM layout is tiled (8, 128), and Mosaic slices it only
at tile bounds.  ``vals`` is treated as non-differentiable data (zero
cotangent): features are inputs, not parameters.

Scalar-prefetch gather (the high-nnz sparse path): the one-hot
densification pays O(bn·jp·bd) VMEM and compute per step, which makes
bag-of-words nnz >= 1k non-viable — ``choose_sparse_blocks`` runs out
of budget.  The ``*_gather`` family instead prefetches the ELL
cols/vals into SMEM (``PrefetchScalarGridSpec``, the pattern from
``mach_candidates.py``) and lets the W BlockSpec index map DMA the
cols[i, j]-th W row directly: forward grid (N, C/bc, jp), one example
row per grid step, the logits tile accumulating rank-1 updates
``v_ij · W[cols_ij, blk]`` in (1, bc) scratch.  Per-step VMEM is O(bc)
— independent of nnz AND of d, so any nnz fits the same budget.  The
backward, grid (N, C/bc, 2·jp), rebuilds the tile in phase 1 (forming
dlogits at its last step, reducing dbias into a zero-aliased revisited
(1, bc) row) and in phase 2 scatter-adds ``dW[cols_ij] += v_ij ·
dlogits`` through gather-indexed output blocks; both grad outputs are
``input_output_aliases``-pinned to zero-filled operands so unvisited W
rows stay zero and every visit is a pure accumulate (duplicate col ids
sum, matching the CSR scatter-add).  The densifying family remains the
low-nnz fast path and, via ``ref.mach_fused_xent_csr_ref``, the parity
oracle; ``ops.mach_fused_xent_csr`` picks between them (``sparse_impl``
knob, auto at ``GATHER_NNZ_THRESHOLD``).

Block choosing: ``choose_fused_blocks`` / ``choose_sparse_blocks``
enumerate candidate tilings in preference order (dense: keep bn large
first — it divides the dominant W stream — then bc, then bd; sparse:
keep bc large first — each column block pays a full densify d-sweep —
then bd, shrinking bn before bd as the one-hot tile grows) and return
the first whose accounted tile bytes (``dense_tile_bytes`` /
``sparse_tile_bytes`` — the superset of either pass's resident VMEM
tiles) fit ``vmem_budget``.  The sparse backward keeps the forward's
column blocks and padded W and picks its own d block
(``choose_sorted_bwd_blocks`` / ``sorted_bwd_tile_bytes``).  A
``ValueError`` is raised only when even the minimum tiling (bn=8,
bc=128, bd at its floor) overflows; explicit ``block_*`` overrides pin
their dimension and the rest shrink around them.

Padding: N pads to bn (padded rows get zero cotangent so contribute
nothing), d pads to a multiple of bd (zero h columns / zero W rows
contribute nothing; dh/dW slices drop them), heads pad to a multiple of
the per-block head count, buckets pad to a multiple of the block width;
padded columns are masked to NEG_INF before the reduction and zeroed in
the backward (so dbias's padded columns are zero too).  Sparse operands
additionally pad J to a lane multiple (padded slots carry val 0).

Every grid is declared ``dimension_semantics=("arbitrary", ...)``: the
scratch statistics and the dW/dbias accumulators need steps in order.
The gather family accumulates dW rows through revisited, gather-indexed
output blocks, which the TPU pipeline does not support;
``ops.mach_fused_xent_csr`` raises on TPU rather than dispatch it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import phase
from repro.kernels.mach_decode import NEG_INF, round_up

_LANE = 128

# Scratch logsumexp state and the revisited dh/dW/dbias output
# accumulators all require grid steps to run in order — declare every
# grid axis "arbitrary" (sequential) so Mosaic may not parallelize or
# reorder them.
_SEQUENTIAL3 = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary"))
_SEQUENTIAL2 = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"))


def _kernel_tags(family: str, kind: str) -> dict:
    """``name=`` and ``metadata=`` of one family's forward or backward
    kernel: the phase tag of ``phase.py``."""
    return dict(name=f"mach_fused_xent_{family}_{kind}",
                metadata=phase.kernel_metadata(
                    phase.LOSS_FWD if kind == "fwd" else phase.LOSS_BWD))


DEFAULT_VMEM_BUDGET = 6 * 2**20

# f32 contractions at f32 precision: the TPU's default rounds matmul
# operands to bf16, which the loss and its gradients must not inherit.
_F32 = jax.lax.Precision.HIGHEST
_dot = functools.partial(jnp.dot, precision=_F32,
                         preferred_element_type=jnp.float32)


def _align_columns(bc_cap: int, r: int, b: int) -> tuple[int, int, int]:
    """Head-align a column-block budget: (bc, rp, bp).  Either whole
    heads per block (bc = nh·b, rp padded to a multiple of nh) or
    bucket-slices of one head (bc | bp, bp the padded per-head width)."""
    if b <= bc_cap:
        nh = max(1, min(bc_cap // b, r))
        return nh * b, round_up(r, nh), b
    return bc_cap, r, round_up(b, bc_cap)


def dense_tile_bytes(bn: int, bc: int, bd: int, rp: int,
                     rows: int = 0) -> int:
    """Accounted VMEM bytes of the dense kernels' resident tiles (f32),
    the max over the forward and backward pass:

    fwd:  h (bn,bd) + W (bd,bc) + bias (1,bc) + y (bn,rp) + loss (bn,1)
          + lse (bn,rp) + acc scratch (bn,bc) + 3 stats (bn,rp)
    bwd:  h + W + bias + y + lse + g (bn,1) + dh (bn,bd) + dW (bd,bc)
          + dbias (1,bc) + acc scratch (bn,bc) + the dlogits scratch of
          every row of one backward call (rows,bc)
    """
    fwd = bn * bd + bd * bc + bc + 5 * bn * rp + bn + bn * bc
    bwd = 2 * bn * bd + 2 * bd * bc + 2 * bc + 2 * bn * rp + 2 * bn \
        + bn * bc + max(rows, bn) * bc
    return 4 * max(fwd, bwd)


def sparse_tile_bytes(bn: int, bc: int, bd: int, rp: int, jp: int,
                      rows: int = 0) -> int:
    """Accounted VMEM bytes of the sparse kernels' resident tiles (f32).
    The per-step densify holds ~two (bn, jp, bd) one-hot intermediates
    on top of the ELL tiles; otherwise as ``dense_tile_bytes`` minus the
    dense dh output.  The ``bwd`` term is that of the d-sweep backward
    the sparse family had; it still bounds the forward's tiling, which
    stays where it was measured.  The backward now accounts its tiles
    in ``sorted_bwd_tile_bytes``."""
    densify = 2 * bn * jp * bd + 2 * bn * jp
    fwd = densify + bd * bc + bc + 5 * bn * rp + bn + bn * bc
    bwd = densify + 2 * bd * bc + 2 * bc + 2 * bn * rp + 2 * bn \
        + bn * bc + max(rows, bn) * bc
    return 4 * max(fwd, bwd)


# Rows per backward call: every row's dlogits tile of one column block
# stays in VMEM scratch between the two backward phases, so larger
# batches run the backward kernel once per chunk of this many rows.
_BWD_ROWS = 512


def _bwd_rows(n: int, bn: int) -> int:
    return min(round_up(n, bn), max(bn, _BWD_ROWS // bn * bn))


def _candidates(override: Optional[int], pool: tuple[int, ...], pref: int,
                granule: int) -> list[int]:
    """Descending candidate sizes: the pinned override alone, or pref
    followed by every smaller pool entry."""
    if override is not None:
        return [max(granule, round_up(override, granule))]
    return [pref] + [x for x in pool if x < pref]


def choose_fused_blocks(n: int, d: int, r: int, b: int,
                        block_n: Optional[int] = None,
                        block_c: Optional[int] = None,
                        block_d: Optional[int] = None,
                        vmem_budget: int = DEFAULT_VMEM_BUDGET
                        ) -> tuple[int, int, int, int, int]:
    """Pick (bn, bc, bd, rp, bp): N block, column block, d block, padded
    head count, padded bucket count — the first candidate tiling whose
    ``dense_tile_bytes`` fit ``vmem_budget``.

    Preference order (first kept large): bn — the W stream is read
    N/bn times, the dominant HBM traffic at LM-scale d; then bc — h is
    re-fetched C/bc times; then bd, which only sets the pipelining
    granularity.  Default bd/bc are lane multiples (each is some tile's
    minor dim); d pads up to a bd multiple.  Explicit ``block_*``
    overrides are honored at sublane (8) granularity — sub-lane minor
    blocks are a test/bench knob for exercising the streaming paths on
    small shapes in interpret mode; pin lane multiples on real TPU
    (Mosaic requires minor block dims of 128·k or the full array dim).
    Raises ``ValueError`` when even the minimum tiling overflows the
    budget."""
    bn_cands = _candidates(block_n, (64, 32, 16, 8),
                           min(128, max(8, round_up(n, 8))), 8)
    bd_full = min(512, round_up(max(d, 1), _LANE))
    bd_cands = _candidates(block_d, (384, 256, 128), bd_full, 8)
    bc_caps = ([max(1, block_c)] if block_c is not None
               else [2048, 1024, 512, 256, 128])
    for bn in bn_cands:
        for bc_cap in bc_caps:
            bc, rp, bp = _align_columns(bc_cap, r, b)
            for bd in bd_cands:
                if dense_tile_bytes(bn, bc, bd, rp,
                                    _bwd_rows(n, bn)) <= vmem_budget:
                    return bn, bc, bd, rp, bp
    bc_min, rp_min, _ = _align_columns(bc_caps[-1], r, b)
    raise ValueError(
        f"no dense fused-xent tiling fits vmem_budget={vmem_budget}: "
        f"minimum candidate (bn={bn_cands[-1]}, bc={bc_min}, "
        f"bd={bd_cands[-1]}) needs "
        f"{dense_tile_bytes(bn_cands[-1], bc_min, bd_cands[-1], rp_min)} "
        f"bytes (n={n}, d={d}, r={r}, b={b})")


def choose_sparse_blocks(n: int, d: int, r: int, b: int, j: int,
                         block_n: Optional[int] = None,
                         block_c: Optional[int] = None,
                         block_d: Optional[int] = None,
                         vmem_budget: int = DEFAULT_VMEM_BUDGET
                         ) -> tuple[int, int, int, int, int, int]:
    """Pick (bn, bc, bd, rp, bp, jp) for the sparse kernels — the first
    candidate tiling whose ``sparse_tile_bytes`` fit ``vmem_budget``.

    The densified (bn, jp, bd) one-hot tile is the VMEM driver.
    Preference order: bc first (every column block pays a full densify
    d-sweep, so fewer blocks = less recompute); then bd, with bn
    shrinking before bd drops (bn is capped at 16 anyway — sublane
    granularity, not W traffic, is the constraint); bd may fall below a
    lane block to the 8-sublane floor at bag-of-words nnz (bd is only
    ever a sublane dim here — the W tile's minor dim is bc).  A
    sub-lane ``block_c`` override is an interpret-mode test knob, as in
    ``choose_fused_blocks``.  Raises ``ValueError`` when even the
    minimum tiling overflows."""
    jp = round_up(max(j, 1), _LANE)
    bn_cands = _candidates(block_n, (8,),
                           min(16, max(8, round_up(n, 8))), 8)
    bd_full = min(512, round_up(max(d, 1), 8))
    bd_cands = _candidates(block_d, (256, 128, 64, 32, 16, 8), bd_full, 8)
    bc_caps = ([max(1, block_c)] if block_c is not None
               else [2048, 1024, 512, 256, 128])
    for bc_cap in bc_caps:
        bc, rp, bp = _align_columns(bc_cap, r, b)
        for bd in bd_cands:
            for bn in bn_cands:
                if sparse_tile_bytes(bn, bc, bd, rp, jp,
                                     _bwd_rows(n, bn)) <= vmem_budget:
                    return bn, bc, bd, rp, bp, jp
    raise ValueError(
        f"no sparse fused-xent tiling fits vmem_budget={vmem_budget} "
        f"(n={n}, d={d}, r={r}, b={b}, nnz_max={j} -> jp={jp})")


# Sorted entries per SMEM block of the sparse backward.
_ENTRY_CHUNK = 1024


def sorted_bwd_tile_bytes(bc: int, bd: int, rp: int, rows: int) -> int:
    """Accounted VMEM bytes of the sparse backward's resident tiles
    (f32): the logits / dlogits scratch of every row of one call
    (rows, bc); the W and dW blocks 4·(bd, bc) and bias and dbias
    4·(1, bc), double-buffered; labels and logsumexp 4·(rows, rp) and
    g 2·(rows, 1).  The sorted entries and the work items ride in SMEM."""
    return 4 * (rows * bc + 4 * bd * bc + 4 * bc + 4 * rows * rp
                + 2 * rows)


def choose_sorted_bwd_blocks(n: int, d: int, bc: int, rp: int,
                             block_d: Optional[int] = None
                             ) -> tuple[int, int]:
    """Pick (bd, rows) for the sparse backward, whose column blocks
    (bc, rp) are the forward's (``choose_sparse_blocks``), so that both
    passes read one padded W: the largest d block whose
    ``sorted_bwd_tile_bytes`` fit ``DEFAULT_VMEM_BUDGET`` (fewer grid
    steps; W and dW move in (bd, bc) blocks either way), and ``rows``
    the rows per backward call (``_BWD_ROWS`` at most).  ``block_d``
    pins bd, as in ``choose_sparse_blocks``.  Raises ``ValueError`` when
    even bd = 8 overflows."""
    rows = _bwd_rows(n, 8)
    for bd in _candidates(block_d, (256, 128, 64, 32, 16, 8),
                          min(512, round_up(max(d, 1), 8)), 8):
        if sorted_bwd_tile_bytes(bc, bd, rp, rows) <= DEFAULT_VMEM_BUDGET:
            return bd, rows
    raise ValueError(
        f"no sparse backward tiling fits {DEFAULT_VMEM_BUDGET} bytes of "
        f"VMEM (n={n}, d={d}, bc={bc}, rp={rp}, rows={rows})")


# nnz at/above which ops.mach_fused_xent_csr auto-routes to the gather
# family: the densify tile's 2·bn·jp·bd term crosses the default budget
# around here, and the gather path's per-step cost (one (1, bc) FMA per
# slot) beats the one-hot contraction well before that.
GATHER_NNZ_THRESHOLD = 512


def gather_tile_bytes(bc: int, rp: int) -> int:
    """Accounted VMEM bytes of the gather kernels' resident tiles (f32),
    the max over the forward and backward pass.  One example row per
    grid step; W streams as a double-buffered (1, bc) row gather — no
    (bn, jp, bd) one-hot tile and no (bd, bc) W tile, so the per-step
    VMEM driver collapses from O(bn·jp·bd) to O(bc), independent of
    both nnz and d:

    fwd:  W row 2·(1,bc) + bias (1,bc) + acc (1,bc) + y (1,rp) + loss
          (1,1) + lse (1,rp) + 3 stats (1,rp)
    bwd:  W row + dW row 2·2·(1,bc) + dbias (1,bc) + bias (1,bc) +
          acc/dlog scratch 2·(1,bc) + y/lse 2·(1,rp) + g (1,1)

    The ELL cols/vals are scalar-prefetch operands and live in SMEM
    (2·4·N·jp bytes), not VMEM — callers account them separately."""
    fwd = 2 * bc + bc + bc + 5 * rp + 1
    bwd = 4 * bc + bc + bc + 2 * bc + 2 * rp + 1
    return 4 * max(fwd, bwd)


def choose_gather_blocks(n: int, d: int, r: int, b: int, j: int,
                         block_c: Optional[int] = None,
                         vmem_budget: int = DEFAULT_VMEM_BUDGET
                         ) -> tuple[int, int, int, int]:
    """Pick (bc, rp, bp, jp) for the gather kernels — the first
    head-aligned column-block candidate whose ``gather_tile_bytes`` fit
    ``vmem_budget``.  nnz never enters the accounting (the ELL operands
    are SMEM scalars; W streams one row at a time), so bag-of-words
    nnz >= 1k fits the same budget as nnz = 8; ``jp`` is only the
    padded grid extent of the nnz axis."""
    jp = max(j, 1)
    bc_caps = ([max(1, block_c)] if block_c is not None
               else [2048, 1024, 512, 256, 128])
    for bc_cap in bc_caps:
        bc, rp, bp = _align_columns(bc_cap, r, b)
        if gather_tile_bytes(bc, rp) <= vmem_budget:
            return bc, rp, bp, jp
    bc, rp, bp = _align_columns(bc_caps[-1], r, b)
    raise ValueError(
        f"no gather fused-xent tiling fits vmem_budget={vmem_budget}: "
        f"minimum candidate bc={bc} needs {gather_tile_bytes(bc, rp)} "
        f"bytes (n={n}, d={d}, r={r}, b={b}, nnz_max={j})")


def _pad_bias(bias, r, b, rp, bp):
    """bias (R·B,) or None -> (1, rp·bp) f32 (zeros when absent — the
    kernels take the operand unconditionally; the add is free)."""
    if bias is None:
        return jnp.zeros((1, rp * bp), jnp.float32)
    b2 = jnp.pad(bias.astype(jnp.float32).reshape(r, b),
                 ((0, rp - r), (0, bp - b)))
    return b2.reshape(1, rp * bp)


def _pad_operands(h2, w, bias, labels, r, b, bn, rp, bp, bd):
    """(h (N,d), w (d,R·B), bias (R·B,)|None, y (N,R)) -> padded
    (h (Np,dp), w (dp,rp·bp), bias (1,rp·bp), y (Np,rp) int32, dp).
    W pads with zero heads/buckets/rows (masked or inert in-kernel),
    labels pad with bucket 0 (their heads are masked)."""
    n, d = h2.shape
    dp = round_up(d, bd)
    npad = -n % bn
    if npad or dp != d:
        h2 = jnp.pad(h2, ((0, npad), (0, dp - d)))
    if npad:
        labels = jnp.pad(labels, ((0, npad), (0, 0)))
    labels = jnp.pad(labels.astype(jnp.int32), ((0, 0), (0, rp - r)))
    w3 = w.reshape(d, r, b)
    w3 = jnp.pad(w3, ((0, dp - d), (0, rp - r), (0, bp - b)))
    return h2, w3.reshape(dp, rp * bp), _pad_bias(bias, r, b, rp, bp), \
        labels, dp


def _pad_sparse_operands(cols, vals, w, bias, labels, r, b, bn, rp, bp,
                         bd, jp):
    """ELL (cols/vals (N,J)), w (d,R·B), bias, y (N,R) -> padded
    (cols/vals (Np,jp), w (dp,rp·bp), bias (1,rp·bp), y (Np,rp), dp).
    Padded slots carry val 0 so they contribute nothing regardless of
    their col id."""
    n, j = cols.shape
    d = w.shape[0]
    dp = round_up(d, bd)
    npad = -n % bn
    cols = jnp.pad(cols.astype(jnp.int32), ((0, npad), (0, jp - j)))
    vals = jnp.pad(vals, ((0, npad), (0, jp - j)))
    labels = jnp.pad(labels.astype(jnp.int32), ((0, npad), (0, 0)))
    labels = jnp.pad(labels, ((0, 0), (0, rp - r)))
    w3 = w.reshape(d, r, b)
    w3 = jnp.pad(w3, ((0, dp - d), (0, rp - r), (0, bp - b)))
    return cols, vals, w3.reshape(dp, rp * bp), \
        _pad_bias(bias, r, b, rp, bp), labels, dp


def _pad_gather_operands(cols, vals, w, bias, labels, r, b, rp, bp, jp):
    """ELL (cols/vals (N, J)), w (d, R·B), bias, y (N, R) -> scalar-
    prefetch operands (cols (N, jp) int32 clamped to [0, d-1], vals
    (N, jp) f32) + padded (w (d, rp·bp), bias (1, rp·bp), y (N, rp)).
    No d or N padding: the gather reads whole W rows one at a time and
    the grid runs one step per example row.  Out-of-range col ids (the
    CSR sentinel ``d``) clamp to d-1 — their val is 0, so the gathered
    row contributes nothing; clamping keeps every prefetched index a
    valid W block id."""
    n, j = cols.shape
    d = w.shape[0]
    cols = jnp.clip(cols.astype(jnp.int32), 0, d - 1)
    cols = jnp.pad(cols, ((0, 0), (0, jp - j)))
    vals = jnp.pad(vals.astype(jnp.float32), ((0, 0), (0, jp - j)))
    labels = jnp.pad(labels.astype(jnp.int32), ((0, 0), (0, rp - r)))
    w3 = jnp.pad(w.reshape(d, r, b), ((0, 0), (0, rp - r), (0, bp - b)))
    return cols, vals, w3.reshape(d, rp * bp), \
        _pad_bias(bias, r, b, rp, bp), labels


def _tile_geometry(bc, bp, kblk):
    """Static (nh, width) + traced (h0, boff) for the current column
    block.  nh heads of ``width`` buckets each; h0 the first head id,
    boff the bucket offset inside it (0 unless a head spans blocks)."""
    nh = max(1, bc // bp)
    width = bp if bc >= bp else bc
    kbase = kblk * bc
    h0 = kbase // bp
    boff = kbase - h0 * bp
    return nh, width, h0, boff


def _column_block(acc, bias_ref, b, bc, bp, jblk):
    """Finished logits of column block ``jblk`` -> (masked tile, bucket
    ids, per-head lane masks, first head id)."""
    nh, width, h0, boff = _tile_geometry(bc, bp, jblk)
    tile, bidx = _finalize_tile(acc, bias_ref, b, width, boff)
    return tile, bidx, _head_lanes(tile.shape, nh, width), h0


def _finalize_tile(acc, bias_ref, b, width, boff):
    """d-accumulated (bn, bc) logits tile + broadcast bias row -> (tile
    with padded buckets at NEG_INF, per-lane bucket ids).  The bias lands
    once, at the last d block, where this is called."""
    tile = acc + bias_ref[...].astype(jnp.float32)      # (bn,bc)+(1,bc)
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    bidx = boff + lane % width
    return jnp.where(bidx < b, tile, NEG_INF), bidx


def _head_lanes(shape, nh, width):
    """Static lane mask of each of the nh heads in a (bn, nh·width) tile
    (None when one head spans the whole tile).  The tile is never
    reshaped to (bn, nh, width): that splits lanes when width < 128,
    which Mosaic refuses."""
    if nh == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return [(lane >= t * width) & (lane < (t + 1) * width)
            for t in range(nh)]


def _masked(mask, x, fill):
    return x if mask is None else jnp.where(mask, x, fill)


def _head_col(x, sel):
    """Column of a (bn, rp) per-head array at the one lane ``sel`` marks
    -> (bn, 1).  A masked lane sum: the head index is traced, and Mosaic
    cannot slice lanes at a traced offset."""
    return jnp.sum(jnp.where(sel, x, jnp.zeros_like(x)), axis=1,
                   keepdims=True)


def _densify_tile(cols_ref, vals_ref, d0, bn, jp, bd):
    """In-VMEM densified activation slice A (bn, bd) for feature range
    [d0, d0+bd): A[n, p] = Σ_j vals[n, j]·1[cols[n, j] = d0+p].  A
    one-hot contraction (no gather — Mosaic-friendly); duplicate ids
    within a row sum, matching a CSR scatter-add; padded slots carry
    val 0 so their col id is irrelevant."""
    local = cols_ref[...].astype(jnp.int32) - d0                # (bn, jp)
    oh = (local[:, :, None] ==
          jax.lax.broadcasted_iota(jnp.int32, (bn, jp, bd), 2))
    weighted = oh.astype(jnp.float32) \
        * vals_ref[...].astype(jnp.float32)[:, :, None]
    return jnp.sum(weighted, axis=1)                            # (bn, bd)


def _online_update(tile, bidx, heads, y_ref, m_scr, s_scr, p_scr, h0):
    """Online per-head (max, sumexp, picked) accumulation on the heads
    this column block touches: ``heads[t]`` masks head h0 + t's lanes."""
    m_all, s_all, p_all = m_scr[...], s_scr[...], p_scr[...]
    y = y_ref[...]
    lane_r = jax.lax.broadcasted_iota(jnp.int32, m_all.shape, 1)
    m_lane = jnp.zeros_like(tile)
    per_head = []
    for t, mask in enumerate(heads):
        sel = lane_r == h0 + t
        m_old = _head_col(m_all, sel)
        m_new = jnp.maximum(m_old, jnp.max(_masked(mask, tile, NEG_INF),
                                           axis=1, keepdims=True))
        m_lane = _masked(mask, m_new, m_lane)
        per_head.append((sel, mask, m_old, m_new, _head_col(y, sel)))
    e = jnp.exp(tile - m_lane)
    for sel, mask, m_old, m_new, y_t in per_head:
        s_t = jnp.sum(_masked(mask, e, 0.0), axis=1, keepdims=True)
        hit = bidx == y_t
        hit = hit if mask is None else hit & mask
        p_t = jnp.sum(jnp.where(hit, tile, 0.0), axis=1, keepdims=True)
        s_new = _head_col(s_all, sel) * jnp.exp(m_old - m_new) + s_t
        s_all = jnp.where(sel, s_new, s_all)
        m_all = jnp.where(sel, m_new, m_all)
        p_all = jnp.where(sel, _head_col(p_all, sel) + p_t, p_all)
    m_scr[...] = m_all
    s_scr[...] = s_all
    p_scr[...] = p_all


def _flush_stats(r, loss_ref, lse_ref, m_scr, s_scr, p_scr):
    """Final reduction: per-head logsumexp -> summed CE + saved lse."""
    lse = m_scr[...] + jnp.log(s_scr[...])                    # (bn, rp)
    head_ok = jax.lax.broadcasted_iota(jnp.int32, lse.shape, 1) < r
    loss_ref[...] = jnp.sum(
        jnp.where(head_ok, lse - p_scr[...], 0.0),
        axis=1, keepdims=True)
    lse_ref[...] = jnp.where(head_ok, lse, 0.0)


def _dlogits_from_tile(tile, bidx, heads, y_ref, lse_ref, g_ref, r, b, h0):
    """g·(softmax − onehot) from a masked (bn, bc) logits tile, zeroed at
    padded heads/buckets."""
    y, lse = y_ref[...], lse_ref[...]
    lane_r = jax.lax.broadcasted_iota(jnp.int32, lse.shape, 1)
    lse_lane = jnp.zeros_like(tile)
    y_lane = jnp.zeros(tile.shape, jnp.int32)
    ok_lane = jnp.zeros(tile.shape, jnp.bool_)
    for t, mask in enumerate(heads):
        sel = lane_r == h0 + t
        lse_lane = _masked(mask, _head_col(lse, sel), lse_lane)
        y_lane = _masked(mask, _head_col(y, sel), y_lane)
        ok = h0 + t < r
        ok_lane = (ok_lane | ok) if mask is None else (ok_lane
                                                       | (mask & ok))
    p = jnp.exp(tile - lse_lane)                              # softmax
    onehot = (bidx == y_lane).astype(jnp.float32)
    return jnp.where((bidx < b) & ok_lane, g_ref[...] * (p - onehot), 0.0)


# ---------------------------------------------------------------------------
# Shared d-blocked kernel steps (dense and sparse differ only in how
# the (bn, bd) activation slice ``a`` is produced: a block load vs an
# in-VMEM ELL densification).
# ---------------------------------------------------------------------------

def _dblocked_fwd_step(a, bn, bc, r, rp, b, bp,
                       w_ref, bias_ref, y_ref, loss_ref, lse_ref,
                       acc_scr, m_scr, s_scr, p_scr):
    """Forward step;  grid (N/bn, C/bc, D/bd), d minor.  The logits
    tile accumulates over d blocks in (bn, bc) scratch; the bias add
    and the online reduction fire once per column block at the last d
    block."""
    jblk = pl.program_id(1)
    kd = pl.program_id(2)
    njb = pl.num_programs(1)
    nkd = pl.num_programs(2)

    @pl.when((jblk == 0) & (kd == 0))
    def _init_stats():
        m_scr[...] = jnp.full((bn, rp), NEG_INF, jnp.float32)
        s_scr[...] = jnp.zeros((bn, rp), jnp.float32)
        p_scr[...] = jnp.zeros((bn, rp), jnp.float32)

    @pl.when(kd == 0)
    def _init_acc():
        acc_scr[...] = jnp.zeros((bn, bc), jnp.float32)

    acc_scr[...] += _dot(a, w_ref[...].astype(jnp.float32))

    @pl.when(kd == nkd - 1)
    def _reduce():
        tile, bidx, heads, h0 = _column_block(acc_scr[...], bias_ref, b,
                                              bc, bp, jblk)
        _online_update(tile, bidx, heads, y_ref, m_scr, s_scr, p_scr, h0)

        @pl.when(jblk == njb - 1)
        def _flush():
            _flush_stats(r, loss_ref, lse_ref, m_scr, s_scr, p_scr)


def _bwd_cell(p, nrows, nkd):
    """Backward grid step p -> (phase 2?, row block i, d block kd).

    Phase 1 (p < nrows·nkd) sweeps the d blocks inside each row block to
    rebuild its logits; phase 2 sweeps the row blocks inside each d
    block, so every dW (and dh) output block is finished in consecutive
    steps and written back once.  A TPU output block is never re-read
    from HBM: an accumulator revisited after its index moved away would
    start from stale VMEM."""
    p1 = nrows * nkd
    grad = p >= p1
    q = jnp.where(grad, p - p1, p)
    i = jnp.where(grad, q % nrows, q // nkd)
    kd = jnp.where(grad, q // nrows, q % nkd)
    return grad, i, kd


def _dblocked_bwd_step(a, nrows, nkd, bn, bc, r, b, bp,
                       w_ref, bias_ref, y_ref, lse_ref, g_ref,
                       dw_ref, db_ref, acc_scr, dlog_scr, dh_ref=None):
    """Single-recompute backward step;  grid (C/bc, 2·nrows·nkd), see
    ``_bwd_cell``.  ``a`` is the activation slice of the step's (row
    block, d block).  Phase 1 rebuilds each row block's logits tile
    once, then at its last d block writes dlogits into the (rows, bc)
    scratch and reduces dbias into the (1, bc) output row.  Phase 2
    accumulates dW_blk += aᵀ @ dlogits over the row blocks of one d
    block and, when ``dh_ref`` is given (dense h), writes this column
    block's dh_blk = dlogits @ Wᵀ (the caller sums the column blocks)."""
    jblk = pl.program_id(0)
    grad, i, kd = _bwd_cell(pl.program_id(1), nrows, nkd)
    rows = pl.ds(pl.multiple_of(i * bn, 8), bn)

    @pl.when(jnp.logical_not(grad))
    def _logits_phase():
        @pl.when(kd == 0)
        def _init():
            acc_scr[...] = jnp.zeros((bn, bc), jnp.float32)

        acc_scr[...] += _dot(a, w_ref[...].astype(jnp.float32))

        @pl.when(kd == nkd - 1)
        def _dlog():
            tile, bidx, heads, h0 = _column_block(acc_scr[...], bias_ref,
                                                  b, bc, bp, jblk)
            dlog = _dlogits_from_tile(tile, bidx, heads, y_ref, lse_ref,
                                      g_ref, r, b, h0)
            dlog_scr[rows, :] = dlog
            db_contrib = jnp.sum(dlog, axis=0, keepdims=True)

            @pl.when(i == 0)
            def _db_first():
                db_ref[...] = db_contrib

            @pl.when(i > 0)
            def _db_acc():
                db_ref[...] += db_contrib

    @pl.when(grad)
    def _grad_phase():
        dlog = dlog_scr[rows, :]
        dw_contrib = jax.lax.dot_general(
            a, dlog,
            dimension_numbers=(((0,), (0,)), ((), ())),
            precision=_F32, preferred_element_type=jnp.float32)  # (bd, bc)

        @pl.when(i == 0)
        def _dw_first():
            dw_ref[...] = dw_contrib

        @pl.when(i > 0)
        def _dw_acc():
            dw_ref[...] += dw_contrib

        if dh_ref is not None:
            dh_ref[0] = jax.lax.dot_general(
                dlog, w_ref[...].astype(jnp.float32),
                dimension_numbers=(((1,), (1,)), ((), ())),
                precision=_F32,
                preferred_element_type=jnp.float32)           # (bn, bd)


# ---------------------------------------------------------------------------
# Dense-h kernel bodies
# ---------------------------------------------------------------------------

def _fwd_body(bn, bc, r, rp, b, bp,
              h_ref, w_ref, bias_ref, y_ref, loss_ref, lse_ref,
              acc_scr, m_scr, s_scr, p_scr):
    """h_ref (bn, bd); w_ref (bd, bc); bias_ref (1, bc); y_ref (bn, rp);
    scratch acc (bn, bc) + stats (bn, rp)."""
    _dblocked_fwd_step(h_ref[...].astype(jnp.float32), bn, bc, r, rp, b,
                       bp, w_ref, bias_ref, y_ref, loss_ref, lse_ref,
                       acc_scr, m_scr, s_scr, p_scr)


def _bwd_body(bn, bc, nrows, nkd, r, b, bp,
              h_ref, w_ref, bias_ref, y_ref, lse_ref, g_ref,
              dh_ref, dw_ref, db_ref, acc_scr, dlog_scr):
    """The h/W index maps follow ``_bwd_cell``, so ``h_ref`` is the
    step's (bn, bd) slice in both phases."""
    _dblocked_bwd_step(h_ref[...].astype(jnp.float32), nrows, nkd, bn, bc,
                       r, b, bp, w_ref, bias_ref, y_ref, lse_ref, g_ref,
                       dw_ref, db_ref, acc_scr, dlog_scr, dh_ref=dh_ref)


# ---------------------------------------------------------------------------
# Sparse-h (padded-ELL) kernel bodies
# ---------------------------------------------------------------------------

def _sparse_fwd_body(bn, bc, bd, r, rp, b, bp, jp,
                     cols_ref, vals_ref, w_ref, bias_ref, y_ref,
                     loss_ref, lse_ref, acc_scr, m_scr, s_scr, p_scr):
    a = _densify_tile(cols_ref, vals_ref, pl.program_id(2) * bd, bn, jp,
                      bd)
    _dblocked_fwd_step(a, bn, bc, r, rp, b, bp, w_ref, bias_ref, y_ref,
                       loss_ref, lse_ref, acc_scr, m_scr, s_scr, p_scr)


# Rows of the work-item table the sparse backward prefetches into SMEM
# (``_work_items``): the item's d block, its entry chunk and the entry
# range [lo, hi) inside the chunk.
_BLK, _CHUNK, _LO, _HI = range(4)


def _sorted_bwd_body(nitems, rows, bc, r, b, bp,
                     items_ref, lc_ref, row_ref, val_ref, w_ref, bias_ref,
                     y_ref, lse_ref, g_ref, dw_ref, db_ref, acc_scr):
    """Sparse backward; grid (C/bc, 2·nitems), the work items of
    ``_work_items`` twice over.  The entries sit in SMEM, sorted by
    feature; an item names its d block k and the range of its entries
    in the current chunk, each with a local col (col - k·bd), a row and
    a val.

    Logits phase (first pass): for each entry, ``acc[row] += val ·
    W_k[col]``, a dynamic row load of the (bd, bc) W block and a row
    add on the (rows, bc) scratch; W passes through VMEM once, not once
    per row block.  At the last item the scratch holds every row's
    logits; it is turned into dlogits in place, 8 rows at a time, and
    dbias is their column sum.  dW phase (second pass): the (bd, bc)
    output block of item k is zeroed at the block's first item, then
    takes ``dW_k[col] += val · dlogits[row]`` for each entry, and is
    written back once when the next block begins.  Duplicate ids,
    within a row or across rows, add in turn."""
    jblk = pl.program_id(0)
    p = pl.program_id(1)
    grad = p >= nitems
    t = jnp.where(grad, p - nitems, p)
    lo, hi = items_ref[_LO, t], items_ref[_HI, t]

    @pl.when(p == 0)
    def _init():
        acc_scr[...] = jnp.zeros((rows, bc), jnp.float32)

    @pl.when(jnp.logical_not(grad))
    def _logits_phase():
        def add(e, carry):
            row = pl.ds(row_ref[e], 1)
            w_row = w_ref[pl.ds(lc_ref[e], 1), :].astype(jnp.float32)
            acc_scr[row, :] += val_ref[e] * w_row
            return carry

        jax.lax.fori_loop(lo, hi, add, 0)

        @pl.when(p == nitems - 1)
        def _dlogits():
            def group(i, db):
                sl = pl.ds(pl.multiple_of(i * 8, 8), 8)
                tile, bidx, heads, h0 = _column_block(
                    acc_scr[sl, :], bias_ref, b, bc, bp, jblk)
                dlog = _dlogits_from_tile(tile, bidx, heads, y_ref.at[sl],
                                          lse_ref.at[sl], g_ref.at[sl], r,
                                          b, h0)
                acc_scr[sl, :] = dlog
                return db + jnp.sum(dlog, axis=0, keepdims=True)

            db_ref[...] = jax.lax.fori_loop(
                0, rows // 8, group, jnp.zeros((1, bc), jnp.float32))

    @pl.when(grad)
    def _grad_phase():
        first = (t == 0) | (items_ref[_BLK, t]
                            != items_ref[_BLK, jnp.maximum(t - 1, 0)])

        @pl.when(first)
        def _zero():
            dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

        def add(e, carry):
            col = pl.ds(lc_ref[e], 1)
            dw_ref[col, :] += val_ref[e] * acc_scr[pl.ds(row_ref[e], 1), :]
            return carry

        jax.lax.fori_loop(lo, hi, add, 0)


# ---------------------------------------------------------------------------
# Scalar-prefetch gather kernel bodies (high-nnz sparse path: no
# densification — W rows are DMA'd by ELL column id via the
# scalar-prefetched index maps in _gather_call).
# ---------------------------------------------------------------------------

def _gather_fwd_body(r, rp, b, bp, bc,
                     cols_sref, vals_sref, w_ref, bias_ref, y_ref,
                     loss_ref, lse_ref, acc_scr, m_scr, s_scr, p_scr):
    """Grid (N, C/bc, jp), nnz minor; one example row per step.  w_ref
    is the (1, bc) slice of the cols[i, jj]-th W row (gathered by the
    BlockSpec index map); the logits tile accumulates rank-1 updates
    ``v·w_row`` across the jp axis in (1, bc) scratch — padded slots
    carry val 0 so their (clamped) col id is irrelevant."""
    i = pl.program_id(0)
    jblk = pl.program_id(1)
    jj = pl.program_id(2)
    njb = pl.num_programs(1)
    nj = pl.num_programs(2)

    @pl.when((jblk == 0) & (jj == 0))
    def _init_stats():
        m_scr[...] = jnp.full((1, rp), NEG_INF, jnp.float32)
        s_scr[...] = jnp.zeros((1, rp), jnp.float32)
        p_scr[...] = jnp.zeros((1, rp), jnp.float32)

    @pl.when(jj == 0)
    def _init_acc():
        acc_scr[...] = jnp.zeros((1, bc), jnp.float32)

    acc_scr[...] += vals_sref[i, jj] * w_ref[...].astype(jnp.float32)

    @pl.when(jj == nj - 1)
    def _reduce():
        tile, bidx, heads, h0 = _column_block(acc_scr[...], bias_ref, b,
                                              bc, bp, jblk)
        _online_update(tile, bidx, heads, y_ref, m_scr, s_scr, p_scr, h0)

        @pl.when(jblk == njb - 1)
        def _flush():
            _flush_stats(r, loss_ref, lse_ref, m_scr, s_scr, p_scr)


def _gather_bwd_body(r, rp, b, bp, bc,
                     cols_sref, vals_sref, w_ref, bias_ref, y_ref,
                     lse_ref, g_ref, dwz_ref, dbz_ref, dw_ref, db_ref,
                     acc_scr, dlog_scr):
    """Grid (N, C/bc, 2·jp).  Phase 1 (k2 < jp) rebuilds the logits
    tile from the gathered rows once; at its last step it forms dlogits
    into (1, bc) scratch and accumulates dbias into the revisited
    (1, bc) output row.  Phase 2 scatter-adds ``dW_row += v·dlogits``
    through the gather-indexed (1, bc) output block — the same
    cols[i, ·]-th row the forward read.  Both grad outputs are
    ``input_output_aliases``-pinned to zero-filled operands
    (``dwz_ref``/``dbz_ref``, never read in-kernel), so unvisited W
    rows stay zero and every visit — duplicate col ids included — is a
    pure accumulate; phase-1 steps map the same dW blocks but leave
    them untouched."""
    del dwz_ref, dbz_ref
    i = pl.program_id(0)
    jblk = pl.program_id(1)
    k2 = pl.program_id(2)
    nj = pl.num_programs(2) // 2

    @pl.when(k2 < nj)
    def _logits_phase():
        @pl.when(k2 == 0)
        def _init():
            acc_scr[...] = jnp.zeros((1, bc), jnp.float32)

        acc_scr[...] += vals_sref[i, k2] * w_ref[...].astype(jnp.float32)

        @pl.when(k2 == nj - 1)
        def _dlog():
            tile, bidx, heads, h0 = _column_block(acc_scr[...], bias_ref,
                                                  b, bc, bp, jblk)
            dlog_scr[...] = _dlogits_from_tile(
                tile, bidx, heads, y_ref, lse_ref, g_ref, r, b, h0)
            db_ref[...] += dlog_scr[...]

    @pl.when(k2 >= nj)
    def _grad_phase():
        dw_ref[...] += vals_sref[i, k2 - nj] * dlog_scr[...]


# ---------------------------------------------------------------------------
# Dense-h entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def mach_fused_xent_pallas(h2: jnp.ndarray, w: jnp.ndarray,
                           bias: Optional[jnp.ndarray],
                           hashed_labels: jnp.ndarray,
                           num_buckets: int,
                           block_n: Optional[int] = None,
                           block_c: Optional[int] = None,
                           block_d: Optional[int] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """Per-example summed R-head CE, straight from hidden states.

    h2 (N, d); w (d, R·B); bias (R·B,) or None (broadcast-added to the
    logits tile in-kernel); hashed_labels (N, R) int32 -> (N,) f32.
    Differentiable: the VJP yields (dh, dW, dbias) without ever forming
    the (N, R·B) logits tensor or a full-d operand tile."""
    out, _ = _fused_fwd(h2, w, bias, hashed_labels, num_buckets, block_n,
                        block_c, block_d, interpret)
    return out


def _fused_call(kind, h2p, wp, biasp, yp, lsep, gp, dims, bn, bc, bd,
                interpret):
    """Shared pallas_call builder for the dense forward/backward."""
    npad, dp, r, rp, b, bp, c = dims
    nkd = dp // bd
    if kind == "fwd":
        h_spec = pl.BlockSpec((bn, bd), lambda i, j, k: (i, k))
        w_spec = pl.BlockSpec((bd, bc), lambda i, j, k: (k, j))
        b_spec = pl.BlockSpec((1, bc), lambda i, j, k: (0, j))
        row_spec = lambda width: pl.BlockSpec((bn, width),
                                              lambda i, j, k: (i, 0))
        return pl.pallas_call(
            functools.partial(_fwd_body, bn, bc, r, rp, b, bp),
            grid=(npad // bn, c // bc, nkd),
            in_specs=[h_spec, w_spec, b_spec, row_spec(rp)],
            out_specs=(row_spec(1), row_spec(rp)),
            out_shape=(jax.ShapeDtypeStruct((npad, 1), jnp.float32),
                       jax.ShapeDtypeStruct((npad, rp), jnp.float32)),
            scratch_shapes=[pltpu.VMEM((bn, bc), jnp.float32)]
            + [pltpu.VMEM((bn, rp), jnp.float32)] * 3,
            compiler_params=_SEQUENTIAL3,
            interpret=interpret,
            **_kernel_tags("dense", kind),
        )(h2p, wp, biasp, yp)
    # bwd: column blocks outer, then the two phases of ``_bwd_cell``
    nrows = npad // bn

    def cell(p):
        return _bwd_cell(p, nrows, nkd)

    def dw_index(j, p):
        grad, _, kd = cell(p)
        return jnp.where(grad, kd, 0), j

    def dh_index(j, p):
        grad, i, kd = cell(p)
        return j, jnp.where(grad, i, 0), jnp.where(grad, kd, 0)

    row_spec = lambda width: pl.BlockSpec((bn, width),
                                          lambda j, p: (cell(p)[1], 0))
    return pl.pallas_call(
        functools.partial(_bwd_body, bn, bc, nrows, nkd, r, b, bp),
        grid=(c // bc, 2 * nrows * nkd),
        in_specs=[pl.BlockSpec((bn, bd), lambda j, p: cell(p)[1:]),
                  pl.BlockSpec((bd, bc), lambda j, p: (cell(p)[2], j)),
                  pl.BlockSpec((1, bc), lambda j, p: (0, j)),
                  row_spec(rp), row_spec(rp), row_spec(1)],
        out_specs=(pl.BlockSpec((1, bn, bd), dh_index),
                   pl.BlockSpec((bd, bc), dw_index),
                   pl.BlockSpec((1, bc), lambda j, p: (0, j))),
        out_shape=(jax.ShapeDtypeStruct((c // bc, npad, dp), jnp.float32),
                   jax.ShapeDtypeStruct((dp, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((bn, bc), jnp.float32),
                        pltpu.VMEM((npad, bc), jnp.float32)],
        compiler_params=_SEQUENTIAL2,
        interpret=interpret,
        **_kernel_tags("dense", kind),
    )(h2p, wp, biasp, yp, lsep, gp)


def _row_chunks(npad: int, rows: int) -> list[slice]:
    return [slice(s, min(s + rows, npad)) for s in range(0, npad, rows)]


def _check_shapes(h2, w, bias, hashed_labels, num_buckets):
    n, d = h2.shape
    r = hashed_labels.shape[-1]
    if hashed_labels.shape != (n, r):
        raise ValueError(f"labels {hashed_labels.shape} vs h {h2.shape}")
    if w.shape != (d, r * num_buckets):
        raise ValueError(f"w {w.shape} != ({d}, {r}*{num_buckets})")
    if bias is not None and bias.shape != (r * num_buckets,):
        raise ValueError(f"bias {bias.shape} != ({r}*{num_buckets},)")
    return n, d, r


@phase.tagged(phase.LOSS_FWD)
def _fused_fwd(h2, w, bias, hashed_labels, num_buckets, block_n, block_c,
               block_d, interpret):
    n, d, r = _check_shapes(h2, w, bias, hashed_labels, num_buckets)
    b = num_buckets
    bn, bc, bd, rp, bp = choose_fused_blocks(n, d, r, b, block_n, block_c,
                                             block_d)
    h2p, wp, biasp, yp, dp = _pad_operands(h2, w, bias, hashed_labels, r,
                                           b, bn, rp, bp, bd)
    dims = (h2p.shape[0], dp, r, rp, b, bp, rp * bp)
    loss, lse = _fused_call("fwd", h2p, wp, biasp, yp, None, None, dims,
                            bn, bc, bd, interpret)
    return loss[:n, 0], (h2, w, bias, hashed_labels, lse[:n])


@phase.tagged(phase.LOSS_BWD)
def _fused_bwd(num_buckets, block_n, block_c, block_d, interpret, res, g):
    h2, w, bias, hashed_labels, lse = res
    n, d, r = _check_shapes(h2, w, bias, hashed_labels, num_buckets)
    b = num_buckets
    bn, bc, bd, rp, bp = choose_fused_blocks(n, d, r, b, block_n, block_c,
                                             block_d)
    h2p, wp, biasp, yp, dp = _pad_operands(h2, w, bias, hashed_labels, r,
                                           b, bn, rp, bp, bd)
    npad = h2p.shape[0]
    dims = (npad, dp, r, rp, b, bp, rp * bp)
    # padded rows/heads carry zero cotangent -> zero dlogits
    gp = jnp.pad(g.astype(jnp.float32).reshape(n, 1),
                 ((0, npad - n), (0, 0)))
    lsep = jnp.pad(lse, ((0, npad - n), (0, 0)))
    parts = [_fused_call("bwd", h2p[sl], wp, biasp, yp[sl], lsep[sl],
                         gp[sl], (sl.stop - sl.start,) + dims[1:], bn, bc,
                         bd, interpret)
             for sl in _row_chunks(npad, _bwd_rows(n, bn))]
    dhp = jnp.concatenate([dh.sum(axis=0) for dh, _, _ in parts])
    dwp = sum(dw for _, dw, _ in parts)
    dbp = sum(db for _, _, db in parts)
    dh = dhp[:n, :d].astype(h2.dtype)
    dw = dwp.reshape(dp, rp, bp)[:d, :r, :b].reshape(d, r * b) \
        .astype(w.dtype)
    if bias is None:
        return dh, dw, None, None
    db = dbp.reshape(rp, bp)[:r, :b].reshape(r * b).astype(bias.dtype)
    return dh, dw, db, None


mach_fused_xent_pallas.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# Sparse-h (padded-ELL) entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def mach_fused_xent_sparse_pallas(cols: jnp.ndarray, vals: jnp.ndarray,
                                  w: jnp.ndarray,
                                  bias: Optional[jnp.ndarray],
                                  hashed_labels: jnp.ndarray,
                                  num_buckets: int,
                                  block_n: Optional[int] = None,
                                  block_c: Optional[int] = None,
                                  block_d: Optional[int] = None,
                                  interpret: bool = False) -> jnp.ndarray:
    """Per-example summed R-head CE from a padded-ELL sparse batch.

    cols/vals (N, J) — row n's active feature ids and weights (padding
    carries val 0; duplicate ids sum); w (d, R·B); bias (R·B,) or None
    (an in-kernel operand — the ELL width stays J, no unit-feature
    column); hashed_labels (N, R) int32 -> (N,) f32.  Neither the
    (N, R·B) logits tensor nor a dense (N, d) activation ever exists in
    HBM in either pass.  Differentiable wrt w and bias only — ``vals``
    is data, not a parameter, and receives a zero cotangent (use the
    densified reference if you need feature grads)."""
    out, _ = _sparse_fwd(cols, vals, w, bias, hashed_labels, num_buckets,
                         block_n, block_c, block_d, interpret)
    return out


def _sparse_call(colsp, valsp, wp, biasp, yp, dims, bn, bc, bd, jp,
                 interpret):
    """pallas_call of the sparse forward."""
    npad, dp, r, rp, b, bp, c = dims
    ell_spec = pl.BlockSpec((bn, jp), lambda i, j, k: (i, 0))
    w_spec = pl.BlockSpec((bd, bc), lambda i, j, k: (k, j))
    b_spec = pl.BlockSpec((1, bc), lambda i, j, k: (0, j))
    row_spec = lambda width: pl.BlockSpec((bn, width),
                                          lambda i, j, k: (i, 0))
    return pl.pallas_call(
        functools.partial(_sparse_fwd_body, bn, bc, bd, r, rp, b, bp, jp),
        grid=(npad // bn, c // bc, dp // bd),
        in_specs=[ell_spec, ell_spec, w_spec, b_spec, row_spec(rp)],
        out_specs=(row_spec(1), row_spec(rp)),
        out_shape=(jax.ShapeDtypeStruct((npad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((npad, rp), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((bn, bc), jnp.float32)]
        + [pltpu.VMEM((bn, rp), jnp.float32)] * 3,
        compiler_params=_SEQUENTIAL3,
        interpret=interpret,
        **_kernel_tags("sparse", "fwd"),
    )(colsp, valsp, wp, biasp, yp)


def _check_sparse_shapes(cols, vals, w, bias, hashed_labels, num_buckets):
    n, j = cols.shape
    d = w.shape[0]
    r = hashed_labels.shape[-1]
    if vals.shape != (n, j):
        raise ValueError(f"vals {vals.shape} vs cols {cols.shape}")
    if hashed_labels.shape != (n, r):
        raise ValueError(f"labels {hashed_labels.shape} vs cols "
                         f"{cols.shape}")
    if w.shape != (d, r * num_buckets):
        raise ValueError(f"w {w.shape} != ({d}, {r}*{num_buckets})")
    if bias is not None and bias.shape != (r * num_buckets,):
        raise ValueError(f"bias {bias.shape} != ({r}*{num_buckets},)")
    return n, d, r, j


@phase.tagged(phase.LOSS_FWD)
def _sparse_fwd(cols, vals, w, bias, hashed_labels, num_buckets, block_n,
                block_c, block_d, interpret):
    n, d, r, j = _check_sparse_shapes(cols, vals, w, bias, hashed_labels,
                                      num_buckets)
    b = num_buckets
    bn, bc, bd, rp, bp, jp = choose_sparse_blocks(n, d, r, b, j, block_n,
                                                  block_c, block_d)
    colsp, valsp, wp, biasp, yp, dp = _pad_sparse_operands(
        cols, vals, w, bias, hashed_labels, r, b, bn, rp, bp, bd, jp)
    dims = (colsp.shape[0], dp, r, rp, b, bp, rp * bp)
    loss, lse = _sparse_call(colsp, valsp, wp, biasp, yp, dims, bn, bc,
                             bd, jp, interpret)
    return loss[:n, 0], (cols, vals, w, bias, hashed_labels, lse[:n])


def _sorted_entries(cols, vals, d, bd, nkd):
    """One backward call's ELL entries, sorted by feature -> (local
    cols, rows, vals), each padded to an ``_ENTRY_CHUNK`` multiple, and
    the work items of ``_work_items``.  The sort is stable, so a block's
    entries keep their row order; slots whose col is outside [0, d)
    (the ELL sentinel ``d``, val 0) sort past the last block."""
    m, j = cols.shape
    end = nkd * bd
    key = jnp.where((cols >= 0) & (cols < d), cols, end).reshape(-1)
    rows = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32)[:, None],
                            (m, j)).reshape(-1)
    key, rows, vals = jax.lax.sort(
        (key.astype(jnp.int32), rows,
         vals.astype(jnp.float32).reshape(-1)), num_keys=1, is_stable=True)
    pad = round_up(m * j, _ENTRY_CHUNK) - m * j
    key = jnp.pad(key, (0, pad), constant_values=end)
    rows = jnp.pad(rows, (0, pad))
    vals = jnp.pad(vals, (0, pad))
    return key % bd, rows, vals, _work_items(key, bd, nkd)


def _work_items(key, bd, nkd):
    """(4, nkd + nchunks) int32 work items over the sorted keys, in d
    block order (rows ``_BLK``, ``_CHUNK``, ``_LO``, ``_HI``).  An item
    starts at each block's first entry and at each chunk's first entry,
    and runs to the next item's start, so it lies in one block and one
    chunk: every block has an item (an empty one when the block has no
    entries) and a block's entries span as many items as chunks.
    Ranges stop at the last entry inside d.  The searches compare every
    key with every bound, (nkd + 1) x entries compares, so that the
    step holds no gather (XLA rewrites one into ops without the phase
    tag) and no device loop (the default binary search is one)."""
    nchunks = key.shape[0] // _ENTRY_CHUNK
    bounds = jnp.arange(nkd + 1, dtype=jnp.int32) * bd
    starts = jnp.searchsorted(key, bounds, method="compare_all")
    inner, stop = starts[1:nkd], starts[nkd:]
    chunk_pos = jnp.arange(nchunks, dtype=jnp.int32) * _ENTRY_CHUNK
    chunk_blk = jnp.searchsorted(inner, chunk_pos, side="right",
                                 method="compare_all")
    pos, blk = jax.lax.sort(
        (jnp.concatenate([starts[:nkd], jnp.minimum(chunk_pos, stop)]),
         jnp.concatenate([jnp.arange(nkd, dtype=jnp.int32), chunk_blk])),
        num_keys=2)
    nxt = jnp.concatenate([pos[1:], stop])
    chunk = jnp.minimum(pos // _ENTRY_CHUNK, nchunks - 1)
    base = chunk * _ENTRY_CHUNK
    return jnp.concatenate([blk, chunk, pos - base, nxt - base]).reshape(
        4, -1)


def _sorted_bwd_call(cols, vals, wp, biasp, yp, lsep, gp, d, r, b, bc, bd,
                     bp, interpret):
    """One sparse backward call over ``cols.shape[0]`` rows -> (dW (d,
    C), dbias (1, C)); ``yp``/``lsep``/``gp`` are padded to a multiple
    of 8 rows."""
    c = wp.shape[1]
    rows = yp.shape[0]
    nkd = pl.cdiv(d, bd)
    lc, er, ev, items = _sorted_entries(cols, vals, d, bd, nkd)
    g = items.shape[1]

    def item(p):
        return jnp.where(p >= g, p - g, p)

    def w_index(j, p, it):
        return it[_BLK, jnp.minimum(p, g - 1)], j

    def dw_index(j, p, it):
        return it[_BLK, item(p)] * (p >= g), j

    entry_spec = pl.BlockSpec((_ENTRY_CHUNK,),
                              lambda j, p, it: (it[_CHUNK, item(p)],),
                              memory_space=pltpu.SMEM)
    whole = lambda width: pl.BlockSpec((rows, width),
                                       lambda j, p, it: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(c // bc, 2 * g),
        in_specs=[entry_spec, entry_spec, entry_spec,
                  pl.BlockSpec((bd, bc), w_index),
                  pl.BlockSpec((1, bc), lambda j, p, it: (0, j)),
                  whole(yp.shape[1]), whole(lsep.shape[1]), whole(1)],
        out_specs=(pl.BlockSpec((bd, bc), dw_index),
                   pl.BlockSpec((1, bc), lambda j, p, it: (0, j))),
        scratch_shapes=[pltpu.VMEM((rows, bc), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_sorted_bwd_body, g, rows, bc, r, b, bp),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((d, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)),
        compiler_params=_SEQUENTIAL2,
        interpret=interpret,
        **_kernel_tags("sparse", "bwd_sorted"),
    )(items, lc, er, ev, wp, biasp, yp, lsep, gp)


@phase.tagged(phase.LOSS_BWD)
def _sparse_bwd(num_buckets, block_n, block_c, block_d, interpret, res, g):
    """Feature-sorted backward (``_sorted_bwd_body``) over the forward's
    column blocks and padded W, which XLA then builds once for both."""
    cols, vals, w, bias, hashed_labels, lse = res
    n, d, r, j = _check_sparse_shapes(cols, vals, w, bias, hashed_labels,
                                      num_buckets)
    b = num_buckets
    bn, bc, bd, rp, bp, jp = choose_sparse_blocks(n, d, r, b, j, block_n,
                                                  block_c, block_d)
    _, _, wp, biasp, yp, _ = _pad_sparse_operands(
        cols, vals, w, bias, hashed_labels, r, b, bn, rp, bp, bd, jp)
    # the kernel loads single W rows, which Mosaic does only for 32-bit
    # dtypes (16-bit ones pack two rows per sublane); for a 16-bit W
    # this writes an f32 copy of the padded W to HBM (d x C x 4 bytes)
    wp = wp.astype(jnp.float32)
    bd, rows = choose_sorted_bwd_blocks(n, d, bc, rp, block_d)
    npad = yp.shape[0]
    # padded rows carry zero cotangent -> zero dlogits
    gp = jnp.pad(g.astype(jnp.float32).reshape(n, 1),
                 ((0, npad - n), (0, 0)))
    lsep = jnp.pad(lse, ((0, npad - n), (0, 0)))
    parts = []
    for s in range(0, n, rows):     # rows is a multiple of 8
        e, sl = min(s + rows, n), slice(s, s + min(rows, round_up(n - s, 8)))
        parts.append(_sorted_bwd_call(
            cols[s:e], vals[s:e], wp, biasp, yp[sl], lsep[sl], gp[sl], d,
            r, b, bc, bd, bp, interpret))
    dwp = sum(dw for dw, _ in parts)
    dbp = sum(db for _, db in parts)
    dw = dwp.reshape(d, rp, bp)[:, :r, :b].reshape(d, r * b).astype(w.dtype)
    # features are data: zero cotangent for vals, none for int cols/labels
    db = (None if bias is None
          else dbp.reshape(rp, bp)[:r, :b].reshape(r * b)
          .astype(bias.dtype))
    return None, jnp.zeros_like(vals), dw, db, None


mach_fused_xent_sparse_pallas.defvjp(_sparse_fwd, _sparse_bwd)


# ---------------------------------------------------------------------------
# Scalar-prefetch gather entry point (high-nnz sparse path)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def mach_fused_xent_gather_pallas(cols: jnp.ndarray, vals: jnp.ndarray,
                                  w: jnp.ndarray,
                                  bias: Optional[jnp.ndarray],
                                  hashed_labels: jnp.ndarray,
                                  num_buckets: int,
                                  block_c: Optional[int] = None,
                                  interpret: bool = False) -> jnp.ndarray:
    """Per-example summed R-head CE from a padded-ELL sparse batch —
    the scalar-prefetch gather family (no densification, no one-hot).

    Same contract as ``mach_fused_xent_sparse_pallas`` (cols/vals
    (N, J); w (d, R·B); optional bias (R·B,); hashed_labels (N, R) ->
    (N,) f32; differentiable wrt w and bias, ``vals`` gets a zero
    cotangent) but the active W rows are DMA'd by ELL column id via
    ``PrefetchScalarGridSpec`` instead of densified in VMEM: per-step
    VMEM is O(bc) — independent of nnz and of d — so high-nnz (>= 1k)
    bag-of-words shapes are first-class.  The ELL cols/vals ride in
    SMEM (2·4·N·J bytes); only ``block_c`` tiles (there is no bn or bd
    here — one example row per grid step, whole W rows per gather).
    Interpret-mode caveat as the module docstring: the zero-aliased
    gather-indexed dW accumulation needs sequential grid order; native
    Mosaic lowering is unvalidated (ROADMAP item 3)."""
    out, _ = _gather_fwd(cols, vals, w, bias, hashed_labels, num_buckets,
                         block_c, interpret)
    return out


def _gather_call(kind, colsp, valsp, wp, biasp, yp, lsep, gp, dims, bc,
                 jp, interpret):
    """Shared pallas_call builder for the gather forward/backward.  The
    scalar-prefetched ``cols`` feed every W/dW BlockSpec index map —
    the DMA gather itself."""
    n, d, r, rp, b, bp, c = dims
    if kind == "fwd":
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, c // bc, jp),
            in_specs=[
                pl.BlockSpec((1, bc),
                             lambda i, j, k, cols, vals: (cols[i, k], j)),
                pl.BlockSpec((1, bc), lambda i, j, k, cols, vals: (0, j)),
                pl.BlockSpec((1, rp), lambda i, j, k, cols, vals: (i, 0)),
            ],
            out_specs=(
                pl.BlockSpec((1, 1), lambda i, j, k, cols, vals: (i, 0)),
                pl.BlockSpec((1, rp), lambda i, j, k, cols, vals: (i, 0)),
            ),
            scratch_shapes=[pltpu.VMEM((1, bc), jnp.float32)]
            + [pltpu.VMEM((1, rp), jnp.float32)] * 3,
        )
        return pl.pallas_call(
            functools.partial(_gather_fwd_body, r, rp, b, bp, bc),
            grid_spec=grid_spec,
            out_shape=(jax.ShapeDtypeStruct((n, 1), jnp.float32),
                       jax.ShapeDtypeStruct((n, rp), jnp.float32)),
            compiler_params=_SEQUENTIAL3,
            interpret=interpret,
            **_kernel_tags("gather", kind),
        )(colsp, valsp, wp, biasp, yp)
    # bwd: both phases of an (i, j) cell map the same gathered dW/W row
    kmap = lambda k2: jnp.where(k2 >= jp, k2 - jp, k2)
    dw_spec = pl.BlockSpec(
        (1, bc), lambda i, j, k2, cols, vals: (cols[i, kmap(k2)], j))
    db_spec = pl.BlockSpec((1, bc), lambda i, j, k2, cols, vals: (0, j))
    row_spec = lambda width: pl.BlockSpec(
        (1, width), lambda i, j, k2, cols, vals: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, c // bc, 2 * jp),
        in_specs=[dw_spec, db_spec, row_spec(rp), row_spec(rp),
                  row_spec(1), dw_spec, db_spec],
        out_specs=(dw_spec, db_spec),
        scratch_shapes=[pltpu.VMEM((1, bc), jnp.float32)] * 2,
    )
    return pl.pallas_call(
        functools.partial(_gather_bwd_body, r, rp, b, bp, bc),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((d, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)),
        # absolute input indices (scalar-prefetch operands included):
        # 7/8 are the zero-filled dW/dbias init operands
        input_output_aliases={7: 0, 8: 1},
        compiler_params=_SEQUENTIAL3,
        interpret=interpret,
        **_kernel_tags("gather", kind),
    )(colsp, valsp, wp, biasp, yp, lsep, gp,
      jnp.zeros((d, c), jnp.float32), jnp.zeros((1, c), jnp.float32))


@phase.tagged(phase.LOSS_FWD)
def _gather_fwd(cols, vals, w, bias, hashed_labels, num_buckets, block_c,
                interpret):
    n, d, r, j = _check_sparse_shapes(cols, vals, w, bias, hashed_labels,
                                      num_buckets)
    b = num_buckets
    bc, rp, bp, jp = choose_gather_blocks(n, d, r, b, j, block_c)
    colsp, valsp, wp, biasp, yp = _pad_gather_operands(
        cols, vals, w, bias, hashed_labels, r, b, rp, bp, jp)
    dims = (n, d, r, rp, b, bp, rp * bp)
    loss, lse = _gather_call("fwd", colsp, valsp, wp, biasp, yp, None,
                             None, dims, bc, jp, interpret)
    return loss[:, 0], (cols, vals, w, bias, hashed_labels, lse)


@phase.tagged(phase.LOSS_BWD)
def _gather_bwd(num_buckets, block_c, interpret, res, g):
    cols, vals, w, bias, hashed_labels, lse = res
    n, d, r, j = _check_sparse_shapes(cols, vals, w, bias, hashed_labels,
                                      num_buckets)
    b = num_buckets
    bc, rp, bp, jp = choose_gather_blocks(n, d, r, b, j, block_c)
    colsp, valsp, wp, biasp, yp = _pad_gather_operands(
        cols, vals, w, bias, hashed_labels, r, b, rp, bp, jp)
    dims = (n, d, r, rp, b, bp, rp * bp)
    gp = g.astype(jnp.float32).reshape(n, 1)
    dwp, dbp = _gather_call("bwd", colsp, valsp, wp, biasp, yp, lse, gp,
                            dims, bc, jp, interpret)
    dw = dwp.reshape(d, rp, bp)[:, :r, :b].reshape(d, r * b) \
        .astype(w.dtype)
    # features are data: zero cotangent for vals, none for int cols/labels
    db = (None if bias is None
          else dbp.reshape(rp, bp)[:r, :b].reshape(r * b)
          .astype(bias.dtype))
    return None, jnp.zeros_like(vals), dw, db, None


mach_fused_xent_gather_pallas.defvjp(_gather_fwd, _gather_bwd)
