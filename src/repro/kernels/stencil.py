"""Sweep-pipelined Pallas TPU stencil kernels with halo reuse.

The kernel realizes the paper's cache-fitting algorithm on the TPU memory
hierarchy (DESIGN.md §2): inputs stay *unblocked* in HBM (ANY memory
space); a VMEM *window* — the tile plus its halo — is the software cache.
The grid sweeps tiles along one axis (the paper's §4 scanning face, chosen
by ``repro.core.tiling.select_tile``'s sweep-aware traffic model), and at
each sweep step the overlap between consecutive windows is **shifted
inside VMEM** instead of re-fetched, so each interior sweep-axis face
crosses the HBM↔VMEM boundary once per sweep instead of twice.  Only the
new slab of ``tile[sweep]`` rows is DMA'd per step — double-buffered into
a landing slab so the next step's fetch overlaps the current compute.

Grid iteration order = sweep order: the sweep axis is the minor-most
(fastest-varying) grid dimension, so scratch windows stay coherent across
consecutive grid steps; every other tile coordinate restarts the sweep
(``k == 0`` reloads the whole window).

**Stage-chain temporal blocking** (DESIGN.md §8–§9): ``time_steps=T > 1``
(or an explicit ``stages=[(offsets, weights), ...]`` chain with a
distinct operator per stage — Runge-Kutta sub-steps, damped-Jacobi
smoother pairs) fuses T consecutive stencil applications into one HBM
pass.  The VMEM window carries the chain's dependency cone (per-dim *sum*
of the per-stage halos), each sweep step still DMAs a single new slab,
and the T−1 intermediate iterates live in staged scratch buffers that
narrow by one stage halo per stage — the trapezoid.  Only the final stage
is written back, so the paper's one-load-per-application charge drops to
one load per T applications.

**Streaming frontiers** (§9): the staged buffers are *frontier rings* —
they persist their valid rows across sweep steps (the same VMEM-shift
idiom the input window uses realizes the ring's rotation).  The first
step of each sweep column computes the full trapezoid once (warm-up);
every later step shifts each frontier by ``tile[sweep]`` rows and
computes only the newly-uncovered rows of each stage — the §8
``∏(1 + Σ_{m>j} h_m_i / T_i)`` redundant recompute drops back to ~1×
flops per application while the HBM traffic is unchanged.  Intermediate
stages are masked to the true grid domain (zero outside), which makes
the fused result exactly equal to iterating the zero-fill reference
stage by stage.

**Ring windows** (DESIGN.md §14, ``window_kind="ring"`` — the default):
along the sweep axis each frontier keeps only the steady-state band its
consumer actually reads — ``tile[sweep] + lo + hi`` rows of the *next*
stage's own halo — instead of the full warm-up trapezoid; the modulo
origin is renormalized to 0 each step by the same VMEM shift, so the
circular addressing costs no dynamic indexing.  VMEM occupancy stops
growing with the remaining chain depth, which roughly doubles the legal
fusion depth at a fixed budget.  ``window_kind="trapezoid"`` keeps the
full-cone buffers (bit-wise identical results — the parity gate).

**Mixed precision** (``dtypes=``): each stage may declare its output
dtype (``None`` = the input's); frontiers are allocated — and the final
stage written back — at the stage dtype, while every stage still
accumulates in f32.  A bf16 input window halves the streamed bytes (and
the dtype-aware planner doubles the sublane grain to match).

Boundary semantics match ``kernels.ref.stencil_ref``: every window cell
outside the grid holds zero, and grids not divisible by the tile compute
zeros in the round-up and trim them.  A **direct launch** (DESIGN.md
§16, ``core.tiling.direct_input``) hands the caller's array to the
kernel as it is; the kernel writes the zeros into its window itself and
DMAs only the grid's cells.  Every other launch embeds each input in a
zero-filled launch buffer (``launch_pads``/``embed_inputs``) with the
window's low halo in front and, behind, the high halo plus the round-up
to whole tiles: a §15 periodic wrap (whose ghost cells the buffer
holds), a §15 int8 hand-off, a §10 sharded launch, and a grid or tiling
off the DMA grain.  §13 boundary ops other than zero fill become
in-kernel correction taps over the same zero-extended window.

**On the chip** (Mosaic): every DMA moves whole (sublane, lane) grains —
8/16/32 sublanes for 4/2/1-byte dtypes, 128 lanes — at offsets that are
grain multiples.  So the VMEM window is the halo'd tile rounded up to the
grain on the last two axes (``core.tiling.window_extents``, the same
extents the planner charges), the logical window sits at its origin, and
a launch buffer carries the trailing slack the last window reads (a
direct launch clips its DMAs to the grid instead); the taps never read
the slack.  A grid off the grain (``core.tiling.grid_slack``) launches
from the buffer, which is whole grains: the chip slices an array only
in whole grains.  A tile off the grain is its axis's only tile, and its
DMA offset on that axis is the constant 0; one with more than one tile
along that axis is refused before compiling.  Each launch passes a
scoped-VMEM limit derived from its buffers, the double-buffered output
block and the body's f32 values, capped by the device's VMEM
(``core.tiling.VMEM_CAPACITY_BYTES``).

**Multi-core sharding** (DESIGN.md §10): sweep columns are independent
even with frontier state (each column warms its own rings at ``k == 0``),
so the cross-axis tile columns can be partitioned over a device mesh.
:mod:`repro.parallel.shard_columns` runs this same sweep kernel
(``padded_call``) on each shard's column slab, with halo exchange only at
shard boundaries.  The kernel itself is shard-agnostic — it receives a
``(d,)`` domain-offset vector in SMEM giving the true-grid coordinate of
the local array's origin (all-zero on a single device), which keeps the
§8/§9 intermediate-stage masks in *global* coordinates under SPMD.

This module is the kernel and its launcher only: it knows nothing of
programs, plans or meshes.  Calls reach it through :mod:`repro.entry`,
which lowers, plans, memoizes and binds them (``bound_call``).
"""

from __future__ import annotations

import functools
import itertools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import (  # shared with the planner
    axis_grain,
    chain_halo,
    direct_input,
    halo_from_offsets,
    kernel_vmem_bytes,
    stage_suffix_halos,
    window_extents,
)

from ._backend import checked_vmem_limit

__all__ = [
    "bound_call",
    "embed_inputs",
    "input_buffer",
    "launch_geometry",
    "launch_pads",
    "padded_call",
    "round_up",
]


def round_up(n: int, t: int) -> int:
    return -(-n // t) * t


class _Stage(NamedTuple):
    """Static per-stage geometry of a fused chain (python ints/arrays).

    ``lo``/``hi`` are this stage's own per-dim halo; ``suffix_lo``/
    ``suffix_hi`` the per-dim sums over the *later* stages (how far their
    dependency cone still reaches past this stage's output); ``ext`` the
    stage's buffer extent ``tile + suffix_lo + suffix_hi`` (the final
    stage's ``ext`` is the bare tile).  ``bc`` is the stage *input*'s
    boundary condition — ``None`` for the engine-native zero fill, else a
    ``(kind, value)`` pair a §13 boundary op lowered to; the kernel
    realizes it as in-kernel correction taps, no host-side pad."""

    offsets: object                 # (s, d) int array
    weights: tuple
    lo: tuple
    hi: tuple
    suffix_lo: tuple
    suffix_hi: tuple
    ext: tuple
    bc: tuple | None = None
    dtype: str | None = None        # stage OUTPUT dtype (None = input's)
    quant: tuple | None = None      # output (scale, zero_point), §15 int8


def _frontier_depth(stages, j, t_s, sweep, window_kind):
    """Sweep-axis extent of frontier buffer j (holding stage j's output,
    feeding stage j+1).  Trapezoid: the full suffix-halo extent.  Ring
    (§14): exactly the band stage j+1's streaming read consumes —
    ``t_s`` plus that stage's *own* sweep halo — which never exceeds the
    trapezoid extent (the suffix sum includes it)."""
    if window_kind == "ring":
        nxt = stages[j + 1]
        return t_s + nxt.lo[sweep] + nxt.hi[sweep]
    return stages[j].ext[sweep]


def _clip_runs(step, base, size, n, count):
    """The static cases of one axis of a direct launch's DMA: for each
    index g < ``count`` the axis range ``[g·step + base, +size)`` clipped
    to the grid's ``[0, n)``, as runs ``(g0, g1, at, ext)`` of
    consecutive indices that fetch ``ext`` cells (0: none) to offset
    ``at`` of the range; the source starts at ``g·step + base + at``."""
    runs = []
    for g in range(count):
        start = g * step + base
        lo, hi = max(start, 0), min(start + size, n)
        case = (lo - start, hi - lo) if hi > lo else (0, 0)
        if runs and runs[-1][2:] == case:
            runs[-1] = (runs[-1][0], g) + case
        else:
            runs.append((g, g) + case)
    return runs


def _when(cond, fn):
    """Run ``fn`` under ``pl.when(cond)``; a ``None`` condition always
    holds."""
    if cond is None:
        fn()
    else:
        pl.when(cond)(fn)


def _sweep_kernel(
    offsets, weights, lo_w, hi_w, stages, tile, sweep, nswp, pipelined,
    window_kind, n_true, in_quant, *refs, direct=False
):
    """Generic d-dim, p-RHS sweep kernel, optionally stage-chain fused.

    refs = (dom_ref, *x_hbm, out_ref, *windows, [*slabs,] *frontiers,
    win_sem, [slab_sem]).  ``dom_ref`` is a ``(d,)`` int32 SMEM vector:
    the true-grid coordinate of local element ``(0, ..., 0)`` of the
    (unpadded) array — all-zero on a single device, the shard's column
    offset under the §10 sharded launch, so the domain masks stay global
    under SPMD.  Each x_hbm is the whole padded array (ANY memory space);
    windows are VMEM refs of the halo'd tile (halo = the chain's summed
    cone ``lo_w``/``hi_w``); slabs are the 2-slot landing buffers for the
    double-buffered next-slab prefetch; frontiers are the ``T - 1``
    narrowing stage buffers holding the intermediate iterates, persisted
    across sweep steps (DESIGN.md §9).

    ``direct`` (DESIGN.md §16): each x_hbm is the caller's array itself,
    not a launch buffer.  The window keeps its layout; the kernel writes
    its cells outside the grid as zeros and DMAs only the grid's own
    cells.  Each DMA lands its cells ``lo_w mod grain`` before their
    place on the last two axes (the chip's DMA lands only on the grain)
    and a VMEM copy moves them home; the taps read the same window.

    ``stages`` is the static per-stage chain (``None`` = single
    application, possibly multi-RHS).  ``window_kind`` sizes the
    frontiers: ``"ring"`` keeps the steady-state band per frontier,
    ``"trapezoid"`` the full warm-up cone (§14) — results are bit-wise
    identical.  ``n_true`` is the unpadded grid shape — intermediate
    stages are masked to it so the fused pass equals iterating the
    zero-fill reference stage by stage.  ``in_quant`` is the launch
    input's affine int8 ``(scale, zero_point)`` when the chain resumes
    from a quantized inter-launch handoff (§15), else ``None``.
    """
    d = len(tile)
    p = len(offsets)
    T = 1 if stages is None else len(stages)
    cross_axes = [i for i in range(d) if i != sweep]
    dom_ref = refs[0]
    x_hbm = refs[1 : p + 1]
    out_ref = refs[p + 1]
    windows = refs[p + 2 : 2 * p + 2]
    pos = 2 * p + 2
    if pipelined:
        slabs = refs[pos : pos + p]
        pos += p
    else:
        slabs = None
    frontiers = refs[pos : pos + (T - 1)]
    pos += T - 1
    if pipelined:
        win_sem, slab_sem = refs[pos:]
    else:
        (win_sem,) = refs[pos:]

    gids = [pl.program_id(j) for j in range(len(cross_axes))]
    k = pl.program_id(len(cross_axes))
    t_s = tile[sweep]
    h_s = lo_w[sweep] + hi_w[sweep]  # total sweep-axis window halo
    reuse = h_s > 0 and nswp > 1
    # The window as allocated: the logical halo'd tile at the origin plus
    # trailing slack up to the DMA grain (``window_extents``).  Every DMA
    # moves whole grains, so its offsets are tile multiples and its
    # extents these rounded ones; each sweep step keeps ``keep`` rows and
    # lands the next ``t_s`` behind them.
    win_ext = tuple(windows[0].shape)
    w_s = win_ext[sweep]
    keep = w_s - t_s
    item = x_hbm[0].dtype.itemsize

    def tile_start(g, i):
        """Where tile ``g`` of axis ``i`` starts in the launch buffer.  A
        tile off the axis's grain is the axis's only tile (on the chip,
        ``_check_dma_grain``), so its start is the constant 0: the chip's
        compiler must prove a DMA offset a multiple of the grain, which
        ``g · tile`` is not."""
        alone = x_hbm[0].shape[i] - win_ext[i] < tile[i]
        if alone and tile[i] % axis_grain(i, d, item):
            return 0
        return g * tile[i]

    def src_index(kk, start, size):
        """HBM index tuple for rows [kk*t_s+start, +size) of the sweep axis
        and the full window cross extents of the current tile."""
        idx = [None] * d
        for j, i in enumerate(cross_axes):
            idx[i] = pl.ds(tile_start(gids[j], i), win_ext[i])
        idx[sweep] = pl.ds(tile_start(kk, sweep) + start, size)
        return tuple(idx)

    def win_part(start, size):
        idx = [slice(None)] * d
        idx[sweep] = pl.ds(start, size)
        return tuple(idx)

    def window_load(kk):
        copies = [
            pltpu.make_async_copy(
                x_hbm[a].at[src_index(kk, 0, w_s)],
                windows[a],
                win_sem.at[a],
            )
            for a in range(p)
        ]
        for cp in copies:
            cp.start()
        return copies

    def slab_copy(a, kk, slot):
        return pltpu.make_async_copy(
            x_hbm[a].at[src_index(kk, keep, t_s)],
            slabs[a].at[slot],
            slab_sem.at[a, slot],
        )

    if direct:
        _direct_loads(
            x_hbm, windows, slabs, win_sem, slab_sem if pipelined else None,
            gids, k, tile, sweep, nswp, lo_w, n_true, reuse,
        )
    elif not reuse:
        # No overlap to reuse (h_s == 0 or a single sweep step): every step
        # fetches its full window.
        for cp in window_load(k):
            cp.wait()
    else:
        @pl.when(k == 0)
        def _():
            copies = window_load(0)
            if pipelined:
                for a in range(p):  # prefetch step 1's slab during compute
                    slab_copy(a, 1, 1 % 2).start()
            for cp in copies:
                cp.wait()

        @pl.when(k > 0)
        def _():
            # Scanning-face reuse: the trailing ``keep`` rows of the
            # previous window (its h_s overlap plus any grain slack) become
            # the leading rows of this one — a VMEM-internal shift, no HBM
            # traffic.
            for a in range(p):
                windows[a][win_part(0, keep)] = windows[a][win_part(t_s, keep)]
            if pipelined:
                for a in range(p):
                    slab_copy(a, k, k % 2).wait()

                @pl.when(k + 1 < nswp)
                def _():
                    for a in range(p):
                        slab_copy(a, k + 1, (k + 1) % 2).start()
                for a in range(p):
                    windows[a][win_part(keep, t_s)] = slabs[a][k % 2]
            else:
                copies = [
                    pltpu.make_async_copy(
                        x_hbm[a].at[src_index(k, keep, t_s)],
                        windows[a].at[win_part(keep, t_s)],
                        win_sem.at[a],
                    )
                    for a in range(p)
                ]
                for cp in copies:
                    cp.start()
                for cp in copies:
                    cp.wait()

    if stages is None:
        # Single application (possibly multi-RHS), engine-native zero
        # boundary: the legacy launch form.
        acc = jnp.zeros(tuple(tile), dtype=jnp.float32)
        for a in range(p):
            x = windows[a][...].astype(jnp.float32)
            for off, w in zip(offsets[a], weights[a]):
                sl = tuple(
                    slice(l + int(o), l + int(o) + t)
                    for o, l, t in zip(off, lo_w, tile)
                )
                acc = acc + np.float32(w) * x[sl]
        out_ref[...] = acc.astype(out_ref.dtype)
        return

    # -- stage-chain trapezoid (p == 1, enforced by the frontend) ----------

    # Periodic wrap (§15) is realized by the host-side ghost fill plus
    # *extended* intermediate-stage masks, never by correction taps: the
    # wrap margin of each iterate is exactly periodic (torus translation
    # invariance), so it must survive the mask for later stages to read.
    periodic = any(
        st.bc is not None and st.bc[0] == "periodic" for st in stages
    )

    def quantize_store(acc, st, dtype):
        """Round/clip the f32 accumulator onto the stage's affine int8
        grid before the storage cast (§15: ``clip(round(x/s) + zp)``,
        half-even like the oracle); a plain dtype cast otherwise."""
        if st.quant is not None:
            s_q, z_q = st.quant
            acc = jnp.clip(
                jnp.round(acc / np.float32(s_q)) + np.float32(int(z_q)),
                -128.0, 127.0,
            )
        return acc.astype(dtype)

    def bc_terms(st, src, out_ext, starts):
        """Correction taps for stage ``st``'s non-zero boundary condition
        (DESIGN.md §13): every read the zero-extended buffer resolved to 0
        but the declared boundary would not.  For each tap and each way it
        can exit the true domain (per-axis side × depth, all corner
        combinations), one position-masked term reads the boundary's
        source cell instead — clamped (neumann), mirrored (reflect), or
        the constant (dirichlet).  Partial corner combinations read cells
        still outside the domain, which the zero-extended buffer holds as
        0, so they self-annihilate; the combination matching a cell's
        actual exit pattern supplies the whole missing value.  All masks
        compare *global* coordinates (``dom_ref``-lifted), so under §10
        sharding corrections fire only on the shards that own a domain
        edge."""
        kind, cval = st.bc
        add = jnp.zeros(out_ext, dtype=jnp.float32)
        pos_cache: dict = {}

        def axis_pos(i):
            if i not in pos_cache:
                pos_cache[i] = (
                    dom_ref[i] + starts[i]
                    + jax.lax.broadcasted_iota(jnp.int32, out_ext, i)
                )
            return pos_cache[i]

        # Robin (u_ghost = α·u_edge + β) decomposes exactly into the two
        # primitives above: a dirichlet-style constant β on every exited
        # read (the affine intercept — applied once per ghost cell, even
        # at corners, matching the oracle's edge-pad-then-mix), plus the
        # neumann clamped-read menu scaled by α (the slope; its partial
        # corner combinations still self-annihilate through the zero
        # buffer, which the fused β term could not).
        mode = "neumann" if kind == "robin" else kind
        gain = np.float32(cval[0]) if kind == "robin" else np.float32(1)
        for off, w in zip(st.offsets, st.weights):
            off = tuple(int(o) for o in off)
            mix = [i for i in range(d) if off[i] != 0]
            if not mix:
                continue  # the center tap never exits the domain
            if kind in ("dirichlet", "robin"):
                # Constant part: one term per tap, on exactly the cells
                # where the read exited the domain.
                c = cval if kind == "dirichlet" else cval[1]
                inside = None
                for i in mix:
                    q = axis_pos(i) + off[i]
                    ok = (q >= 0) & (q < n_true[i])
                    inside = ok if inside is None else inside & ok
                add = add + jnp.where(
                    inside,
                    jnp.float32(0),
                    np.float32(w) * np.float32(c),
                )
                if kind == "dirichlet":
                    continue
            # neumann (edge-replicate) / reflect (mirror about the edge
            # node): per-axis menus of (global output plane, corrected
            # offset) for each exit depth e — low side reads u[-e] from
            # plane -off_i - e, high side u[n-1+e] from plane n-1+e-off_i.
            menus = []
            for i in mix:
                opts: list = [None]
                o = off[i]
                if o < 0:
                    for e in range(1, -o + 1):
                        oc = o + e if mode == "neumann" else o + 2 * e
                        opts.append((-o - e, oc))
                else:
                    for e in range(1, o + 1):
                        oc = o - e if mode == "neumann" else o - 2 * e
                        opts.append((n_true[i] - 1 + e - o, oc))
                menus.append(opts)
            for combo in itertools.product(*menus):
                if all(c is None for c in combo):
                    continue
                oc = list(off)
                mask = None
                for i, c in zip(mix, combo):
                    if c is None:
                        continue
                    plane, o_corr = c
                    oc[i] = o_corr
                    eq = axis_pos(i) == plane
                    mask = eq if mask is None else mask & eq
                sl = tuple(
                    slice(l + int(o), l + int(o) + e)
                    for o, l, e in zip(oc, st.lo, out_ext)
                )
                add = add + jnp.where(
                    mask, gain * np.float32(w) * src[sl], jnp.float32(0)
                )
        return add

    def stage_apply(j, src, out_ext, starts):
        """Apply stage j's operator over ``out_ext`` output points.  The
        source block is laid out so that output element 0 sits at source
        coordinate ``lo_j`` per dim — true for the full previous buffer in
        warm-up AND for the trailing frontier block when streaming.
        ``starts`` is the true-grid coordinate of output element 0 per dim
        (pre-``dom_ref``), used only by the boundary correction taps."""
        st = stages[j]
        src = src.astype(jnp.float32)
        q_src = in_quant if j == 0 else stages[j - 1].quant
        if q_src is not None:
            # §15: the source block holds affine int8 codes — dequantize
            # once into the f32 MAC path ((q − zp)·scale), so the taps
            # and the boundary corrections all read real values.
            src = (src - np.float32(int(q_src[1]))) * np.float32(q_src[0])
        acc = jnp.zeros(out_ext, dtype=jnp.float32)
        for off, w in zip(st.offsets, st.weights):
            sl = tuple(
                slice(l + int(o), l + int(o) + e)
                for o, l, e in zip(off, st.lo, out_ext)
            )
            acc = acc + np.float32(w) * src[sl]
        if st.bc is not None and st.bc[0] != "periodic":
            # Periodic needs no taps: its ghost values are materialized
            # by the wrap fill and kept alive by the extended masks.
            acc = acc + bc_terms(st, src, out_ext, starts)
        return acc

    def mask_domain(acc, starts, ext, st):
        """Zero everything outside the true grid (coordinates here are
        true-grid: the domain is [0, n_true_i) per axis; ``dom_ref`` lifts
        the local ``starts`` into that global frame) — the zero-fill
        boundary every intermediate iterate must carry.  Under periodic
        wrap (§15) the kept region widens to the stage's suffix margin
        ``[-suffix_lo_i, n_true_i + suffix_hi_i)``: those margin values
        are exact periodic images the later stages read in place of
        correction taps, while the round-up slack beyond still zeroes."""
        inside = None
        for i in range(d):
            if lo_w[i] + hi_w[i] == 0:
                # No stage mixes along this axis: pad/slack stays exactly
                # zero through every stage, so no mask is needed.
                continue
            posn = (
                dom_ref[i] + starts[i]
                + jax.lax.broadcasted_iota(jnp.int32, ext, i)
            )
            lob, hib = 0, n_true[i]
            if periodic:
                lob = -st.suffix_lo[i]
                hib = n_true[i] + st.suffix_hi[i]
            ok = (posn >= lob) & (posn < hib)
            inside = ok if inside is None else inside & ok
        if inside is None:
            return acc
        return jnp.where(inside, acc, jnp.zeros_like(acc))

    def stage_starts(j, streamed):
        """True-grid coordinates of element 0 of stage j's computed block:
        the full ``ext`` trapezoid in warm-up (sweep start ``k·t_s −
        suffix_lo``), the t_s newly-uncovered rows at the frontier's
        leading edge when streaming (sweep start ``k·t_s + suffix_hi``)."""
        st = stages[j]
        starts = [None] * d
        for idx, i in enumerate(cross_axes):
            starts[i] = gids[idx] * tile[i] - st.suffix_lo[i]
        if streamed:
            starts[sweep] = k * t_s + st.suffix_hi[sweep]
        else:
            starts[sweep] = k * t_s - st.suffix_lo[sweep]
        return starts

    def full_compute():
        """The §8 trapezoid: every stage over its full extent — the warm-up
        of each sweep column (and the whole story when there is no sweep
        overlap to stream across).  Under the §14 ring only the trailing
        steady-state band of each stage's value is *stored*; the full
        extent is passed forward as a value, round-tripped through the
        frontier dtype so the stored rows and the forwarded block agree
        bit-wise with the trapezoid's read-back."""
        cur = windows[0][...]
        for j in range(T):
            acc = stage_apply(j, cur, stages[j].ext, stage_starts(j, False))
            if j < T - 1:
                acc = mask_domain(
                    acc, stage_starts(j, False), stages[j].ext, stages[j]
                )
                # Round-trip through the staged scratch in the frontier
                # dtype so the fused chain matches separate kernel
                # launches bit-wise (each launch writes its iterate in
                # the stage dtype — quantized onto the int8 grid first
                # when the stage carries a §15 quantization).
                stored = quantize_store(acc, stages[j], frontiers[j].dtype)
                depth_j = _frontier_depth(stages, j, t_s, sweep, window_kind)
                if depth_j == stages[j].ext[sweep]:
                    frontiers[j][...] = stored
                    cur = frontiers[j][...]
                else:
                    sl = [slice(None)] * d
                    sl[sweep] = slice(
                        stages[j].ext[sweep] - depth_j, stages[j].ext[sweep]
                    )
                    frontiers[j][...] = stored[tuple(sl)]
                    cur = stored
            else:
                out_ref[...] = quantize_store(acc, stages[j], out_ref.dtype)

    def streaming_step():
        """The §9 streaming wavefront: rotate each frontier ring by t_s
        rows and compute only the newly-uncovered rows of each stage —
        stage j consumes exactly the trailing ``t_s + lo_j + hi_j`` rows
        of stage j−1's frontier (the window for j = 0).  Under the §14
        ring that trailing band IS the whole buffer."""
        for j in range(T):
            st = stages[j]
            blk = t_s + st.lo[sweep] + st.hi[sweep]
            if j == 0:
                src_ref = windows[0]
                src_len = t_s + h_s
            else:
                src_ref = frontiers[j - 1]
                src_len = _frontier_depth(
                    stages, j - 1, t_s, sweep, window_kind
                )
            src = src_ref[win_part(src_len - blk, blk)]
            out_ext = tuple(
                t_s if i == sweep else st.ext[i] for i in range(d)
            )
            acc = stage_apply(j, src, out_ext, stage_starts(j, True))
            if j < T - 1:
                # Ring rotation, realized as the same VMEM shift the input
                # window uses: drop the t_s oldest rows, keep the rest
                # (the modulo origin renormalized to 0 each step).
                depth_j = _frontier_depth(stages, j, t_s, sweep, window_kind)
                keep = depth_j - t_s
                if keep > 0:
                    frontiers[j][win_part(0, keep)] = (
                        frontiers[j][win_part(t_s, keep)]
                    )
                acc = mask_domain(acc, stage_starts(j, True), out_ext, st)
                frontiers[j][win_part(max(keep, 0), t_s)] = (
                    quantize_store(acc, st, frontiers[j].dtype)
                )
            else:
                out_ref[...] = quantize_store(acc, st, out_ref.dtype)

    if not reuse:
        # No persisted overlap (h_s == 0 or a single sweep step): there is
        # no frontier state to stream from; every step is a warm-up.
        full_compute()
    else:
        @pl.when(k == 0)
        def _():
            full_compute()

        @pl.when(k > 0)
        def _():
            streaming_step()


def _direct_loads(x_hbm, windows, slabs, win_sem, slab_sem, gids, k, tile,
                  sweep, nswp, lo_w, n_true, reuse):
    """The window fill of a direct launch (DESIGN.md §16): the same
    windows, slabs and sweep-step order as the launch-buffer path, filled
    from the caller's arrays.

    Every DMA fetches only cells inside the grid.  On an axis in one tile
    the window's low halo is off the grid, so a DMA lands the cells
    ``r = lo_w mod grain`` early (on the grain), and a VMEM copy moves
    them home (``r`` is 0 elsewhere: ``core.tiling.direct_input`` admits
    a split axis only behind a whole-grain low halo).  Zeros cover the
    rest of the window: once per sweep column around the cells the first
    fill lands, on the strip a move leaves behind, and on the landing
    rows of sweep steps past the grid's end — the VMEM shift keeps them.
    Static cases (``_clip_runs``) select each DMA's extents by tile and
    step index, so a start and its wait always agree."""
    d = len(tile)
    p = len(windows)
    cross_axes = [i for i in range(d) if i != sweep]
    win_ext = tuple(windows[0].shape)
    t_s = tile[sweep]
    w_s = win_ext[sweep]
    keep = w_s - t_s
    item = x_hbm[0].dtype.itemsize
    sft = tuple(lo % axis_grain(i, d, item) for i, lo in enumerate(lo_w))

    def span(b):
        return tuple(pl.ds(a, n) for a, n in b)

    def rows(start, size):
        return span([
            (start, size) if i == sweep else (0, e)
            for i, e in enumerate(win_ext)
        ])

    def landed(box):
        return [(a - r, n) for (a, n), r in zip(box, sft)]

    def hull(box):  # the landed cells and their home
        return [(a - r, n + r) for (a, n), r in zip(box, sft)]

    # Per cross axis: the runs of tile indices whose window holds the
    # same grid cells, as (condition, source start, (home, size)).
    cross_opts = []
    for j, i in enumerate(cross_axes):
        nt = -(-n_true[i] // tile[i])
        opts = []
        for g0, g1, at, n in _clip_runs(
            tile[i], -lo_w[i], win_ext[i], n_true[i], nt
        ):
            cond = (
                None if (g0, g1) == (0, nt - 1)
                else (gids[j] >= g0) & (gids[j] <= g1)
            )
            opts.append((cond, gids[j] * tile[i] - lo_w[i] + at, (at, n)))
        cross_opts.append(opts)

    def all_of(conds):
        conds = [c for c in conds if c is not None]
        return functools.reduce(jnp.logical_and, conds) if conds else None

    def cases(kk, r0, size, d0):
        """Sweep rows ``[kk·t_s + r0 − lo_s, +size)`` of the tile's
        window, homed at row ``d0``: ``(condition, source, home box)``
        per static case; rows off the grid are not fetched."""
        sweep_opts = []
        for g0, g1, at, n in _clip_runs(
            t_s, r0 - lo_w[sweep], size, n_true[sweep], nswp
        ):
            if n == 0:
                continue
            if isinstance(kk, int):
                if not g0 <= kk <= g1:
                    continue
                cond = None
            elif (g0, g1) == (0, nswp - 1):
                cond = None
            else:
                cond = (kk >= g0) & (kk <= g1)
            sweep_opts.append(
                (cond, kk * t_s + r0 - lo_w[sweep] + at, (d0 + at, n))
            )
        out = []
        for combo in itertools.product(sweep_opts, *cross_opts):
            src = [None] * d
            box = [None] * d
            for i, (_, start, b) in zip([sweep] + cross_axes, combo):
                src[i] = pl.ds(start, b[1])
                box[i] = b
            out.append((all_of(c for c, _, _ in combo), tuple(src), box))
        return out

    def copy(a, src, dst, sem):
        return pltpu.make_async_copy(x_hbm[a].at[src], dst, sem)

    def zeros_over(ref, box):
        ref[span(box)] = jnp.zeros(tuple(n for _, n in box), ref.dtype)

    def settle(ref, box):
        """Move the cells landed early home and zero the strip left."""
        if not any(sft):
            return
        ref[span(box)] = ref[span(landed(box))]
        for i, r in enumerate(sft):
            if r:
                strip = hull(box)
                strip[i] = (box[i][0] - r, r)
                zeros_over(ref, strip)

    def window_load(kk):
        """Fill whole windows: zeros around the landing hull, then the
        DMAs; the caller waits and settles."""
        loads = []
        for cond, src, box in cases(kk, 0, w_s, 0):
            around = hull(box)
            for a in range(p):
                cp = copy(a, src, windows[a].at[span(landed(box))],
                          win_sem.at[a])

                def start(a=a, cp=cp, around=around):
                    for i in range(d):
                        lo, n = around[i]
                        for at, size in ((0, lo),
                                         (lo + n, win_ext[i] - lo - n)):
                            if size > 0:
                                part = [
                                    around[j] if j < i else (at, size)
                                    if j == i else (0, win_ext[j])
                                    for j in range(d)
                                ]
                                zeros_over(windows[a], part)
                    cp.start()

                _when(cond, start)
                loads.append((cond, a, cp, box))
        return loads

    def finish(loads):
        for cond, a, cp, box in loads:
            def done(a=a, cp=cp, box=box):
                cp.wait()
                settle(windows[a], box)
            _when(cond, done)

    def slab_copies(a, kk, slot):
        return [
            (cond, copy(a, src, slabs[a].at[(slot,) + span(landed(box))],
                        slab_sem.at[a, slot]))
            for cond, src, box in cases(kk, keep, t_s, 0)
        ]

    def run(copies, op):
        for cond, cp in copies:
            _when(cond, getattr(cp, op))

    def zero_rows_past_grid():
        """Zeros over landing rows whose source lies past the grid's end
        (a sweep column's tail): they hold the last step's rows."""
        for g0, g1, _, n in _clip_runs(
            t_s, keep - lo_w[sweep], t_s, n_true[sweep], nswp
        ):
            if n == t_s or g1 < 1:
                continue

            def body(n=n):
                for a in range(p):
                    zeros_over(windows[a], [
                        (keep + n, t_s - n) if i == sweep else (0, e)
                        for i, e in enumerate(win_ext)
                    ])

            _when((k >= g0) & (k <= g1), body)

    if not reuse:
        finish(window_load(k))
        return

    @pl.when(k == 0)
    def _():
        loads = window_load(0)
        if slabs is not None:
            for a in range(p):  # prefetch step 1's slab during compute
                run(slab_copies(a, 1, 1), "start")
        finish(loads)

    @pl.when(k > 0)
    def _():
        for a in range(p):  # the scanning-face reuse, as on the buffer
            windows[a][rows(0, keep)] = windows[a][rows(t_s, keep)]
        if slabs is not None:
            for a in range(p):
                run(slab_copies(a, k, k % 2), "wait")

            @pl.when(k + 1 < nswp)
            def _():
                for a in range(p):
                    run(slab_copies(a, k + 1, (k + 1) % 2), "start")

            # Only the slab's cells inside the grid land, moved home: the
            # window's zero margins across stay as they are.
            for combo in itertools.product(*cross_opts):
                box = [(keep, t_s)] * d
                for i, (_, _, b) in zip(cross_axes, combo):
                    box[i] = b
                src = [(0, t_s)] * d
                for i in cross_axes:
                    src[i] = landed(box)[i]

                def land(box=box, src=src):
                    for a in range(p):
                        windows[a][span(box)] = slabs[a][(k % 2,) + span(src)]

                _when(all_of(c for c, _, _ in combo), land)
        else:
            loads = []
            for cond, src, box in cases(k, keep, t_s, keep):
                for a in range(p):
                    cp = copy(a, src, windows[a].at[span(landed(box))],
                              win_sem.at[a])
                    _when(cond, cp.start)
                    loads.append((cond, a, cp, box))
            finish(loads)
        zero_rows_past_grid()


def launch_geometry(offsets_w, stages_w, tile, bcs_w=None, dtypes_w=None,
                     quants_w=None):
    """Static launch geometry shared by the single-device and sharded
    paths: per-RHS offset/weight arrays, the per-stage chain (``None`` =
    single application), and the window cone ``lo_w``/``hi_w`` — the same
    helpers the planner prices VMEM/traffic with, so kernel geometry and
    planned geometry cannot diverge.  ``bcs_w`` attaches each stage
    input's lowered boundary condition (``None`` entries = native zero
    fill); ``dtypes_w`` each stage's output dtype name (``None`` entries
    = the launch input's dtype); ``quants_w`` each stage output's affine
    int8 ``(scale, zero_point)`` (``None`` entries = unquantized)."""
    d = len(tile)
    if stages_w is not None:
        T = len(stages_w)
        st_offs = [np.asarray(s[0], dtype=np.int64).reshape(-1, d)
                   for s in stages_w]
        st_wts = [tuple(float(w) for w in s[1]) for s in stages_w]
        st_halos = [halo_from_offsets([o], d) for o in st_offs]
        st_bcs = tuple(bcs_w) if bcs_w is not None else (None,) * T
        assert len(st_bcs) == T, (st_bcs, T)
        st_dts = tuple(dtypes_w) if dtypes_w is not None else (None,) * T
        assert len(st_dts) == T, (st_dts, T)
        st_qns = tuple(quants_w) if quants_w is not None else (None,) * T
        assert len(st_qns) == T, (st_qns, T)
        cone = chain_halo(st_halos)
        lo_w = tuple(lo for lo, _ in cone)
        hi_w = tuple(hi for _, hi in cone)
        suffix = stage_suffix_halos(st_halos)
        stages = []
        for j in range(T):
            sfx_lo = tuple(lo for lo, _ in suffix[j])
            sfx_hi = tuple(hi for _, hi in suffix[j])
            stages.append(_Stage(
                offsets=st_offs[j],
                weights=st_wts[j],
                lo=tuple(h[0] for h in st_halos[j]),
                hi=tuple(h[1] for h in st_halos[j]),
                suffix_lo=sfx_lo,
                suffix_hi=sfx_hi,
                ext=tuple(
                    t + l + h for t, l, h in zip(tile, sfx_lo, sfx_hi)
                ),
                bc=st_bcs[j],
                dtype=st_dts[j],
                quant=st_qns[j],
            ))
        stages = tuple(stages)
        offsets = [st_offs[0]]
        weights = [list(st_wts[0])]
    else:
        T = 1
        stages = None
        offsets = [np.asarray(ow[0], dtype=np.int64).reshape(-1, d)
                   for ow in offsets_w]
        weights = [list(ow[1]) for ow in offsets_w]
        halo = halo_from_offsets(offsets, d)
        lo_w = tuple(h[0] for h in halo)
        hi_w = tuple(h[1] for h in halo)
    return offsets, weights, stages, lo_w, hi_w


def padded_call(ins, dom, offsets, weights, stages, lo_w, hi_w, tile,
                 sweep, pipelined, interpret, n_true,
                 window_kind="ring", in_quant=None, direct=False):
    """Run the sweep kernel over already-padded arrays and return the
    *padded* result (``∏ ntiles_i · tile_i`` per dim, no trim).

    ``ins`` carry the window halo on every dim (``lo_w_i + k_i·tile_i +
    hi_w_i``); callers own padding and trimming so the §10 sharded launch
    can substitute halo *exchange* for the shard-axis pad.  ``dom`` is the
    traced ``(d,)`` int32 true-grid coordinate of local element 0 (zeros
    on a single device) and ``n_true`` the *global* unpadded grid shape —
    together they keep the intermediate-stage domain masks global under
    ``shard_map``.

    ``direct=True`` (DESIGN.md §16; only for a launch that
    ``core.tiling.direct_input`` admits) takes the caller's arrays as
    they are instead, and the kernel fills the windows' zeros itself."""
    d = len(tile)
    p = len(ins)
    T = 1 if stages is None else len(stages)
    u0 = ins[0]
    window_shape = window_extents(
        tile, list(zip(lo_w, hi_w)), u0.dtype.itemsize
    )
    if direct:
        ntiles = tuple(-(-int(n) // t) for n, t in zip(u0.shape, tile))
    else:
        ntiles = tuple(
            (u0.shape[i] - window_shape[i]) // tile[i] + 1 for i in range(d)
        )
    if not interpret:
        _check_dma_grain(tile, ntiles, u0.dtype.itemsize)
    nswp = ntiles[sweep]
    cross_axes = [i for i in range(d) if i != sweep]
    grid = tuple(ntiles[i] for i in cross_axes) + (nswp,)
    pipelined = bool(pipelined) and nswp > 1 and (lo_w[sweep] + hi_w[sweep]) > 0

    slab_shape = tuple(
        tile[sweep] if i == sweep else window_shape[i] for i in range(d)
    )
    scratch = [pltpu.VMEM(window_shape, u0.dtype) for _ in range(p)]
    if pipelined:
        scratch += [pltpu.VMEM((2,) + slab_shape, u0.dtype) for _ in range(p)]
    # Frontier buffers, persisted across sweep steps (§9 streaming): a
    # trapezoid keeps tile + suffix halo per dim; a §14 ring keeps only
    # the steady-state band along the sweep axis.  Each frontier lives in
    # its own stage's dtype (None = the input's).
    t_s = tile[sweep]
    for j in range(T - 1):
        f_ext = list(stages[j].ext)
        f_ext[sweep] = _frontier_depth(stages, j, t_s, sweep, window_kind)
        f_dtype = (
            jnp.dtype(stages[j].dtype) if stages[j].dtype else u0.dtype
        )
        scratch.append(pltpu.VMEM(tuple(f_ext), f_dtype))
    scratch.append(pltpu.SemaphoreType.DMA((p,)))
    if pipelined:
        scratch.append(pltpu.SemaphoreType.DMA((p, 2)))
    out_dtype = (
        jnp.dtype(stages[-1].dtype)
        if stages is not None and stages[-1].dtype
        else u0.dtype
    )

    def out_index_map(*g):
        idx = [None] * d
        for j, i in enumerate(cross_axes):
            idx[i] = g[j]
        idx[sweep] = g[-1]
        return tuple(idx)

    return pl.pallas_call(
        functools.partial(
            _sweep_kernel, offsets, weights, lo_w, hi_w, stages, tile,
            sweep, nswp, pipelined, window_kind,
            tuple(int(n) for n in n_true), in_quant, direct=direct,
        ),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pl.ANY) for _ in ins],
        out_specs=pl.BlockSpec(tile, out_index_map),
        out_shape=jax.ShapeDtypeStruct(
            tuple(k * t for k, t in zip(ntiles, tile)), out_dtype
        ),
        scratch_shapes=scratch,
        compiler_params=(
            None if interpret else pltpu.CompilerParams(
                vmem_limit_bytes=_vmem_limit(
                    tile, lo_w, hi_w, u0.dtype.itemsize, sweep, pipelined,
                    p, stages, window_kind,
                )
            )
        ),
        interpret=interpret,
        name="stencil_sweep",
    )(dom, *ins)


def _vmem_limit(tile, lo_w, hi_w, itemsize, sweep, pipelined, p, stages,
                window_kind):
    """The launch's scoped-VMEM limit: the planner's own kernel model
    (``core.tiling.kernel_vmem_bytes``) at this launch's geometry."""
    halo = list(zip(lo_w, hi_w))
    stage_halos = stage_dbs = None
    if stages is not None:
        stage_halos = [list(zip(st.lo, st.hi)) for st in stages]
        stage_dbs = [
            jnp.dtype(st.dtype).itemsize if st.dtype else itemsize
            for st in stages
        ]
    return checked_vmem_limit(kernel_vmem_bytes(
        tile, halo, itemsize, sweep, pipelined, p,
        stage_halos=stage_halos, window_kind=window_kind,
        stage_dtype_bytes=stage_dbs,
    ))


def _check_dma_grain(tile, ntiles, itemsize):
    """Refuse, before compiling, a tile the chip's DMA cannot address:
    with more than one tile along an axis, the tile offsets must be
    multiples of that axis's (sublane, lane) grain."""
    d = len(tile)
    for i, (t, n) in enumerate(zip(tile, ntiles)):
        g = axis_grain(i, d, itemsize)
        if n > 1 and t % g:
            raise ValueError(
                f"tile {tuple(tile)} is not a multiple of the {g}-element "
                f"grain on axis {i} ({n} tiles along it): the chip's DMA "
                "cannot address it; use an aligned tile or interpret mode"
            )


def input_buffer(shape, tile, halo, itemsize, bcs_w=None, in_quant=None,
                 num_shards=1):
    """What one launch reads its input from: ``"direct"``, the caller's
    array as it is (``core.tiling.direct_input``, DESIGN.md §16), or a
    launch buffer built by ``"wrap"`` (§15 periodic ghost fill),
    ``"embed"`` (zeros plus one update, §13 boundary programs) or
    ``"pad"`` (``jnp.pad``)."""
    if direct_input(shape, tile, halo, itemsize, bcs_w, in_quant,
                    num_shards):
        return "direct"
    bcs = [bc for bc in (bcs_w or ()) if bc is not None]
    if any(bc[0] == "periodic" for bc in bcs):
        return "wrap"
    return "embed" if bcs else "pad"


def launch_pads(shape, tile, lo_w, hi_w, itemsize):
    """Per-dim ``(lo, hi)`` extension of an array into its launch buffer:
    the window's low halo in front, and behind the content enough for
    the last tile's whole DMA window (``window_extents``) — the high halo,
    the round-up to whole tiles and the grain slack."""
    ext = window_extents(tile, list(zip(lo_w, hi_w)), itemsize)
    return [
        (lo, round_up(int(n), t) - t + e - lo - int(n))
        for n, t, lo, e in zip(shape, tile, lo_w, ext)
    ]


def embed_inputs(us, pads, pad_free=False, wrap=None, fill=0):
    """Zero-extend each array into its launch buffer: per-dim ``(lo,
    hi)`` extra extent, content at offset ``lo``, zeros elsewhere — the
    one input prep both the single-device and §10 sharded paths share.

    ``pad_free=False`` is the legacy ``jnp.pad`` spelling.  With
    ``pad_free=True`` (boundary-op programs, DESIGN.md §13) the same
    buffer is built as an allocation plus one ``dynamic_update_slice`` —
    bit-identical values, no host-side pad op on the hot path (boundary
    values come from in-kernel correction taps, not from materialized
    ghost cells).

    ``wrap`` (per-dim ``(lo, hi)`` ghost extents, §15 periodic) fills
    each ghost band from the far side of the domain instead of leaving
    it at the fill value; ``fill`` sets the background (the int8 zero
    point for a quantized inter-launch handoff, so the slack dequantizes
    to exact zeros).

    The ops carry the scopes ``stencil_embed`` (the buffer) and
    ``stencil_wrap`` (the ghost fill) in their metadata, which a profiler
    trace keeps, so that the launch's copies can be told apart there."""
    with jax.named_scope("stencil_embed"):
        if not pad_free:
            bufs = (
                [jnp.pad(u, pads, constant_values=fill) for u in us]
                if fill else [jnp.pad(u, pads) for u in us]
            )
        else:
            shape = tuple(
                int(n) + lo + hi for (lo, hi), n in zip(pads, us[0].shape)
            )
            starts = tuple(lo for lo, _ in pads)
            bufs = [
                jax.lax.dynamic_update_slice(
                    jnp.full(shape, fill, u.dtype) if fill
                    else jnp.zeros(shape, u.dtype),
                    u, starts,
                )
                for u in us
            ]
    if wrap is None:
        return bufs

    def wrap_fill(buf, n_shape):
        # Copy each ghost band from the far side of the domain, axis by
        # axis: axis k's copies read ghost rows axes < k already filled,
        # which reproduces ``np.pad(mode="wrap")``'s corner composition
        # exactly.  Round-up slack past the high ghost stays at fill.
        d = len(n_shape)
        for i, (lo, hi) in enumerate(wrap):
            n = int(n_shape[i])
            base = pads[i][0]
            if lo:
                dst = [slice(None)] * d
                src = [slice(None)] * d
                dst[i] = slice(base - lo, base)
                src[i] = slice(base + n - lo, base + n)
                buf = buf.at[tuple(dst)].set(buf[tuple(src)])
            if hi:
                dst = [slice(None)] * d
                src = [slice(None)] * d
                dst[i] = slice(base + n, base + n + hi)
                src[i] = slice(base, base + hi)
                buf = buf.at[tuple(dst)].set(buf[tuple(src)])
        return buf

    with jax.named_scope("stencil_wrap"):
        return [wrap_fill(buf, u.shape) for buf, u in zip(bufs, us)]


@functools.partial(
    jax.jit,
    static_argnames=(
        "offsets_w", "tile", "sweep", "pipelined", "interpret", "stages_w",
        "bcs_w", "dtypes_w", "window_kind", "quants_w", "in_quant",
    ),
)
def _stencil_call(us, offsets_w, tile, sweep, pipelined, interpret,
                  stages_w=None, bcs_w=None, dtypes_w=None,
                  window_kind="ring", quants_w=None, in_quant=None):
    """us: tuple of p same-shape arrays.  offsets_w: tuple per array of
    (offsets_tuple, weights_tuple) — hashable static spec.  ``stages_w``
    (tuple per stage of (offsets_tuple, weights_tuple), single RHS only)
    fuses the whole chain into this one launch: one HBM pass, T
    applications with streaming per-stage frontiers.  ``bcs_w`` (tuple
    per stage, ``None``/``(kind, value)``) attaches lowered §13 boundary
    conditions; any non-zero entry switches the input prep to the
    pad-free embed.  ``dtypes_w`` (tuple per stage, ``None``/dtype name)
    sets each stage's output dtype; ``window_kind`` picks the §14 ring
    (default) or the full trapezoid frontier layout.  ``quants_w``
    (tuple per stage, ``None``/``(scale, zero_point)``) quantizes each
    stage's stored output onto the affine int8 grid, and ``in_quant``
    declares the launch *input*'s quantization when it is a quantized
    inter-launch handoff (§15).

    A launch that ``core.tiling.direct_input`` admits hands the caller's
    arrays to the kernel as they are (DESIGN.md §16); every other one
    builds the launch buffer ``input_buffer`` names first."""
    u0 = us[0]
    d = u0.ndim
    tile = tuple(int(t) for t in tile)
    offsets, weights, stages, lo_w, hi_w = launch_geometry(
        offsets_w, stages_w, tile, bcs_w, dtypes_w, quants_w
    )
    buf = input_buffer(
        u0.shape, tile, list(zip(lo_w, hi_w)), u0.dtype.itemsize, bcs_w,
        in_quant,
    )
    if buf == "direct":
        ins = us
    else:
        ins = embed_inputs(
            us, launch_pads(u0.shape, tile, lo_w, hi_w, u0.dtype.itemsize),
            pad_free=buf != "pad",
            wrap=tuple(zip(lo_w, hi_w)) if buf == "wrap" else None,
            fill=int(in_quant[1]) if in_quant is not None else 0,
        )
    out = padded_call(
        ins, jnp.zeros((d,), jnp.int32), offsets, weights, stages, lo_w,
        hi_w, tile, sweep, pipelined, interpret, u0.shape,
        window_kind=window_kind, in_quant=in_quant,
        direct=buf == "direct",
    )
    with jax.named_scope("stencil_trim"):
        return out[tuple(slice(0, n) for n in u0.shape)]


@functools.lru_cache(maxsize=128)
def bound_call(statics: tuple):
    """``_stencil_call`` with its static arguments (``statics``, sorted
    ``(name, value)`` pairs) bound once: the jitted dispatch then sees
    only the arrays.  It traces the same function under the same name,
    so it compiles what ``_stencil_call`` compiles."""
    fn = _stencil_call.__wrapped__
    bound = functools.partial(fn, **dict(statics))
    return jax.jit(functools.update_wrapper(bound, fn))


def __getattr__(name):
    # Forwarder for bench/harness.py's make_entry, which imports
    # stencil_iterate from here: resolved once, then a module global.
    if name == "stencil_iterate":
        from repro.entry import stencil_iterate

        globals()[name] = stencil_iterate
        return stencil_iterate
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
