"""TPU VMEM tile selection — the cache-fitting argument on a software cache.

This is the DESIGN.md §2 adaptation of the paper's §4: on TPU the fast
memory is explicitly managed, so "cache loads" become HBM→VMEM DMA bytes
and the fitting problem becomes *tile-shape selection*.

Two traffic models are supported (DESIGN.md §3):

* **per-tile-halo** (``sweep_axis=None``): every tile is DMA'd with its
  full halo, so each interior face is fetched twice (once by each
  neighbor).  This was the seed's only model.

      traffic(T) = prod_i ceil(N_i/T_i) · prod_i (T_i + h_lo_i + h_hi_i)

* **sweep-reuse** (``sweep_axis=s``): tiles are swept along axis ``s``
  and the overlap between consecutive tiles along the sweep axis is kept
  resident in VMEM (the paper's §4 scanning face), so the sweep-axis halo
  is charged once per sweep column instead of once per tile:

      traffic(T) = prod_{i≠s} ceil(N_i/T_i)
                   · (N'_s + h_lo_s + h_hi_s) · prod_{i≠s} (T_i + h_lo_i + h_hi_i)

  with N'_s the sweep extent rounded up to T_s (the kernel's pad path).

Both minimize subject to bytes(operand tile incl. halo and the prefetch
slabs) ≤ VMEM budget / n_operands — the paper's surface-to-volume
argument with the fundamental parallelepiped replaced by an axis-aligned
box (DMA engines move rectangles; a skew parallelepiped is not DMA-able).
The isoperimetric lower bound of §3 still applies and we report the
achieved/optimal ratio.  The multi-operand budget split mirrors §5
(p RHS arrays ⇒ S/p per array).

**Temporal blocking** (``time_steps=T > 1``, DESIGN.md §8): one fused
sweep applies the stencil T times before anything returns to HBM, so the
paper's one-load-per-application charge drops to one load per *T*
applications.  The price is a T-deep trapezoid: every halo grows to
``T·(h_lo, h_hi)`` in the traffic model, and the VMEM footprint adds the
T−1 staged intermediate windows (stage j keeps ``T_i + (T−j)(h_lo+h_hi)``
per dim).  ``tile_traffic_bytes(..., time_steps=T)`` prices the whole
fused pass — T applications in one HBM sweep — so comparing it against
``T ×`` the single-pass figure is the fused-vs-unfused decision the plan
compiler makes.

**Stage chains** (DESIGN.md §9): the fused pass may apply a *different*
operator at each of the T stages (Runge-Kutta sub-steps, damped-Jacobi
smoother pairs).  Every model function accepts ``stage_halos`` — an
ordered list of per-stage per-dim ``(lo, hi)`` halos — in place of the
homogeneous ``halo × time_steps`` scaling: the window halo becomes the
*sum* of the per-stage halos (the chain's dependency cone), and stage j's
staged buffer keeps the suffix sum of the later stages' halos.  For a
homogeneous chain the two spellings agree exactly.

**Compute model** (:func:`chain_flops`): the §8 trapezoid *recomputes*
every intermediate stage inside each window's overlap — the
``∏(1 + Σ_{m>j} h_m_i / T_i)`` per-stage overhead.  The §9 streaming
kernel persists per-stage frontiers across sweep steps, so after the
per-column warm-up each stage computes only its ``T_s`` newly-uncovered
rows.  ``chain_flops(..., streaming=True/False)`` models both, letting
the plan compiler surface the flops the streaming path gives back at
unchanged traffic.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from math import prod
from typing import Sequence

import numpy as np

from .isoperimetric import lower_bound_loads

__all__ = [
    "TileChoice",
    "DEFAULT_VMEM_BUDGET",
    "TARGET_DEVICE_KIND",
    "TARGET_VMEM_BYTES",
    "VMEM_CAPACITY_BYTES",
    "WINDOW_KINDS",
    "axis_grain",
    "candidate_tiles",
    "chain_flops",
    "chain_halo",
    "direct_input",
    "dtype_itemsize",
    "fused_halo",
    "fused_stage_bytes",
    "grid_slack",
    "halo_from_offsets",
    "kernel_vmem_bytes",
    "stage_suffix_halos",
    "sublane_unit",
    "tile_traffic_bytes",
    "tile_vmem_bytes",
    "surface_to_volume",
    "select_tile",
    "vmem_capacity_bytes",
    "window_extents",
]

# VMEM per TensorCore, keyed by ``jax.Device.device_kind`` (Google Cloud
# TPU documentation, "TPU v5e": 128 MiB of VMEM per core).  A device kind
# missing here is an error, never a guess: add its row before running on
# it.
VMEM_CAPACITY_BYTES = {
    "TPU v5 lite": 128 * 1024 * 1024,
}
# The chip the planner sizes for when no TPU is attached: interpret mode
# on the CPU emulates this device's kernels.
TARGET_DEVICE_KIND = "TPU v5 lite"
LANE = 128
SUBLANE = 8


def vmem_capacity_bytes(device_kind: str) -> int:
    """VMEM bytes of one core of ``device_kind`` (from the table above)."""
    try:
        return VMEM_CAPACITY_BYTES[str(device_kind)]
    except KeyError:
        raise ValueError(
            f"no VMEM capacity known for device kind {device_kind!r}; "
            f"known kinds: {sorted(VMEM_CAPACITY_BYTES)}"
        ) from None


TARGET_VMEM_BYTES = vmem_capacity_bytes(TARGET_DEVICE_KIND)
# The planner's default budget: half of the target core's VMEM, leaving
# the other half to the kernel's in-body temporaries and the compiler.
DEFAULT_VMEM_BUDGET = TARGET_VMEM_BYTES // 2
# Compiler-internal scratch on top of the modeled kernel footprint.
KERNEL_VMEM_SLACK = 8 * 1024 * 1024

# Staged-intermediate window layouts (DESIGN.md §14): the §8/§9 trapezoid
# keeps stage j's full suffix-halo extent resident; the ring keeps only the
# steady-state band the next stage's streaming read actually consumes.
WINDOW_KINDS = ("trapezoid", "ring")

# Element sizes of the dtypes the engine accepts, keyed by canonical name.
# numpy has no bfloat16, so this table (not np.dtype) is the single source
# for the plan stack; the kernel side resolves names through jnp.dtype.
_DTYPE_BYTES = {
    "float64": 8, "int64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1,
}


def dtype_itemsize(name: str) -> int:
    """Bytes per element of a canonical dtype name (bfloat16-aware)."""
    try:
        return _DTYPE_BYTES[str(name)]
    except KeyError:
        raise ValueError(
            f"unsupported dtype {name!r}; expected one of "
            f"{sorted(_DTYPE_BYTES)}"
        ) from None


def sublane_unit(dtype_bytes: int) -> int:
    """Minimum second-minor tile grain for a packed dtype: the TPU packs
    ``4 // itemsize`` elements per 32-bit register row, so bf16 wants
    sublane multiples of 16 and int8 of 32 (f32 stays at 8).  The lane
    grain is always :data:`LANE`."""
    return SUBLANE * max(1, 4 // max(int(dtype_bytes), 1))


def axis_grain(axis: int, d: int, dtype_bytes: int) -> int:
    """Layout grain of one axis of a d-dim array: the chip tiles the last
    two axes as (sublane, lane) = (:func:`sublane_unit`, :data:`LANE`);
    leading axes are untiled (grain 1).  A DMA's offsets and extents must
    be multiples of the grain on every axis."""
    if axis == d - 1:
        return LANE
    if axis == d - 2:
        return sublane_unit(dtype_bytes)
    return 1


def window_extents(
    tile: Sequence[int],
    halo: Sequence[tuple[int, int]],
    dtype_bytes: int,
    aligned: bool = True,
) -> tuple[int, ...]:
    """Per-dim extent of the VMEM window the sweep kernel DMAs for one
    tile: ``tile + lo + hi``, rounded up to :func:`axis_grain` when
    ``aligned`` (what the chip's DMA engine can move).  The logical window
    sits at the window's origin; the round-up is trailing slack the taps
    never read.  The kernel allocates exactly this and the planner
    charges exactly this, so the two cannot diverge."""
    d = len(tile)
    ext = []
    for i, (t, (lo, hi)) in enumerate(zip(tile, halo)):
        e = int(t) + int(lo) + int(hi)
        if aligned:
            g = axis_grain(i, d, dtype_bytes)
            e = -(-e // g) * g
        ext.append(e)
    return tuple(ext)


def grid_slack(shape: Sequence[int], dtype_bytes: int) -> tuple[int, int]:
    """``(sublanes, lanes)``: on each of the grid's last two axes, the
    cells between the grid's end and the end of its last (sublane, lane)
    grain (:func:`axis_grain`), which a grain-rounded window reaching the
    grid's end covers and the output never holds.  ``(0, 0)`` for a grid
    of whole grains; a 1-d grid has no sublane axis.  A grid with slack
    launches from a buffer (:func:`direct_input`); the launcher's
    ``kernel_launch`` span and the plan report (``repro.plan.explain``)
    both give this figure."""
    d = len(shape)
    slack = [0, 0]
    for i in range(max(d - 2, 0), d):
        g = axis_grain(i, d, dtype_bytes)
        slack[i - d + 2] = -int(shape[i]) % g
    return tuple(slack)


def direct_input(
    shape: Sequence[int],
    tile: Sequence[int],
    halo: Sequence[tuple[int, int]],
    dtype_bytes: int,
    bcs: Sequence | None = None,
    in_quant: tuple | None = None,
    num_shards: int = 1,
) -> bool:
    """Whether a launch reads the caller's array as it is, with no
    zero-filled launch buffer (DESIGN.md §16): the kernel writes the
    window's zeros outside the grid itself.  It does when

    * the fill is zero: no §15 int8 hand-off (``in_quant``), whose fill
      is the zero point;
    * no stage wraps periodically (§15): those ghost cells are copies of
      the far side, not zeros;
    * it runs on one device: a §10 shard's slab is built by the halo
      exchange;
    * every DMA from the array stays on the (sublane, lane) grain
      (:func:`axis_grain`): each of the last two axes is a whole number
      of grains (no :func:`grid_slack`: the chip's compiler slices an
      array only in whole grains, even along a whole axis), and an axis
      split into several tiles is split at grain multiples behind a low
      halo of whole grains.  (An axis in one tile lands its cells up to
      a grain early and the kernel shifts them in VMEM.)

    The window is the one :func:`window_extents` gives either way, so
    the planner's VMEM charge holds for both.  The launcher, its
    ``kernel_launch`` span and the plan report (``repro.plan.explain``)
    all decide with this one function."""
    if in_quant is not None or int(num_shards) > 1:
        return False
    if bcs and any(bc is not None and bc[0] == "periodic" for bc in bcs):
        return False
    if any(grid_slack(shape, dtype_bytes)):
        return False
    d = len(shape)
    for i, (n, t, (lo, _)) in enumerate(zip(shape, tile, halo)):
        g = axis_grain(i, d, dtype_bytes)
        if -(-int(n) // int(t)) > 1 and (int(t) % g or int(lo) % g):
            return False
    return True


def halo_from_offsets(
    offsets_list: Sequence, d: int
) -> list[tuple[int, int]]:
    """Per-dim asymmetric halo (lo, hi) covering every offset of every RHS:
    lo_i = max(0, -min o_i), hi_i = max(0, max o_i).

    The single definition shared by the sweep kernel (window shapes) and
    the plan compiler (VMEM/traffic model) — they must agree or the
    planner budgets windows the kernel does not allocate.
    """
    lo = [0] * d
    hi = [0] * d
    for offs in offsets_list:
        offs = np.asarray(offs, dtype=np.int64).reshape(-1, d)
        for i in range(d):
            lo[i] = max(lo[i], int(max(0, -offs[:, i].min(initial=0))))
            hi[i] = max(hi[i], int(max(0, offs[:, i].max(initial=0))))
    return list(zip(lo, hi))


@dataclass(frozen=True)
class TileChoice:
    tile: tuple[int, ...]
    grid: tuple[int, ...]
    traffic_bytes: int
    vmem_bytes: int
    surface_to_volume: float
    lower_bound_bytes: float
    efficiency: float  # lower_bound / achieved traffic  (1.0 = optimal)
    sweep_axis: int | None = None  # axis with halo reuse; None = per-tile halo

    def __post_init__(self):
        # The isoperimetric bound is a true lower bound on any schedule, so
        # the modeled traffic of a concrete legal schedule can never beat it.
        assert 0.0 <= self.efficiency <= 1.0, (
            f"efficiency {self.efficiency} > 1: traffic model fell below the "
            f"isoperimetric lower bound (tile={self.tile})"
        )


def _aligned_candidates(n: int, unit: int, cap: int) -> list[int]:
    """Tile extents to consider for one dim: unit-aligned sizes plus n."""
    cands = {min(n, cap)}
    t = unit
    while t < min(n, cap):
        cands.add(t)
        t *= 2
    # Non-power-of-two aligned sizes help when n mod 2^k is bad.
    for mult in (3, 5, 6, 12, 24):
        v = unit * mult
        if v <= min(n, cap):
            cands.add(v)
    cands.add(min(n, cap))
    if n <= cap:
        cands.add(n)
    return sorted(cands)


def _free_candidates(n: int, cap: int) -> list[int]:
    """Unaligned extents (powers of two + n) — for modeling a scalar cache
    (the paper's S) where no lane/sublane constraint applies."""
    cands = {min(n, cap)}
    t = 1
    while t < min(n, cap):
        cands.add(t)
        t *= 2
    if n <= cap:
        cands.add(n)
    return sorted(cands)


def candidate_tiles(
    shape: Sequence[int],
    max_tile_elems: int,
    sweep_axis: int | None = None,
    aligned: bool = True,
    dtype_bytes: int = 4,
) -> list[tuple[int, ...]]:
    """Candidate tiles.  ``aligned=True`` restricts to hardware-aligned
    extents (lane dim multiples of 128, sublane dim multiples of the
    dtype's packed grain — 8 for f32, 16 for bf16, 32 for int8 — leading
    dims small integers).  The sweep axis additionally admits small
    extents: with halo reuse the sweep tile only amortizes the window
    shift, so thin slabs (the paper's scanning face) are often optimal.
    """
    d = len(shape)
    per_dim: list[list[int]] = []
    for i, n in enumerate(shape):
        if not aligned:
            opts = set(_free_candidates(n, max_tile_elems))
        elif i == d - 1:
            opts = set(_aligned_candidates(n, LANE, max_tile_elems))
        elif i == d - 2:
            opts = set(
                _aligned_candidates(n, sublane_unit(dtype_bytes),
                                    max_tile_elems)
            )
        else:
            opts = {o for o in (1, 2, 4, 8, 16, 32, 64, 128, n) if o <= n}
        if i == sweep_axis and (not aligned or i < d - 2):
            # Thin sweep slabs — but never below the lane/sublane grain
            # when hardware alignment is requested: a 1-wide lane DMA
            # still moves a full vector, so the thin-tile traffic model
            # would be unachievable there.
            opts |= {o for o in (1, 2, 4, 8) if o <= n}
        per_dim.append(sorted(opts))
    return [t for t in itertools.product(*per_dim)]


def surface_to_volume(
    tile: Sequence[int], halo: Sequence[tuple[int, int]]
) -> float:
    """Halo-weighted surface-to-volume ratio of an axis-aligned tile:

        Σ_i (h_lo_i + h_hi_i) · prod_{j≠i} T_j  /  prod_i T_i

    i.e. the face loads proper, without the corner/edge cross terms the
    (halo'd volume)/volume − 1 expression over-counts.
    """
    vol = prod(tile)
    surf = sum(
        (lo + hi) * prod(t for j, t in enumerate(tile) if j != i)
        for i, (lo, hi) in enumerate(halo)
    )
    return surf / vol


def fused_halo(
    halo: Sequence[tuple[int, int]], time_steps: int
) -> list[tuple[int, int]]:
    """Halo of the T-step fused trapezoid: each application consumes one
    stencil halo, so the input window needs ``T·(h_lo, h_hi)`` per dim."""
    return [(lo * time_steps, hi * time_steps) for lo, hi in halo]


def chain_halo(
    stage_halos: Sequence[Sequence[tuple[int, int]]]
) -> list[tuple[int, int]]:
    """Window halo of a fused stage chain: the per-dim *sum* of the
    per-stage halos — each stage consumes its own halo off the dependency
    cone.  For T copies of one halo this equals :func:`fused_halo`."""
    d = len(stage_halos[0])
    return [
        (
            sum(int(h[i][0]) for h in stage_halos),
            sum(int(h[i][1]) for h in stage_halos),
        )
        for i in range(d)
    ]


def stage_suffix_halos(
    stage_halos: Sequence[Sequence[tuple[int, int]]]
) -> list[list[tuple[int, int]]]:
    """Per-stage suffix halos of a chain: entry j (0-indexed) is the
    per-dim ``(Σ_{m>j} lo_m, Σ_{m>j} hi_m)`` — how far stage j+1..T's
    dependency cone still reaches past stage j+1's output.  Stage j+1's
    staged buffer/computed extent is ``tile + suffix[j]`` per dim, and the
    last entry is all-zero (the final stage computes the bare tile)."""
    T = len(stage_halos)
    d = len(stage_halos[0])
    out: list[list[tuple[int, int]]] = []
    for j in range(T):
        out.append(
            [
                (
                    sum(int(stage_halos[m][i][0]) for m in range(j + 1, T)),
                    sum(int(stage_halos[m][i][1]) for m in range(j + 1, T)),
                )
                for i in range(d)
            ]
        )
    return out


def tile_traffic_bytes(
    shape: Sequence[int],
    tile: Sequence[int],
    halo: Sequence[tuple[int, int]],
    dtype_bytes: int,
    sweep_axis: int | None = None,
    time_steps: int = 1,
    stage_halos: Sequence[Sequence[tuple[int, int]]] | None = None,
) -> int:
    """Total HBM→VMEM bytes of one pass of the engine: ``time_steps``
    stencil applications fused into a single sweep of the array.

    ``sweep_axis=None`` charges the full halo on every tile (per-tile-halo
    model).  ``sweep_axis=s`` reuses the overlap between consecutive tiles
    along axis ``s`` so its halo is charged once per sweep column.
    ``time_steps=T > 1`` grows every halo T× (the trapezoid's dependency
    cone) but the returned bytes then pay for T applications, not one.
    ``stage_halos`` prices a heterogeneous stage chain instead: the window
    halo is the per-stage sum and the pass pays for ``len(stage_halos)``
    applications (``halo``/``time_steps`` are ignored).
    """
    halo = (
        chain_halo(stage_halos)
        if stage_halos is not None
        else fused_halo(halo, time_steps)
    )
    ntiles = [-(-n // t) for n, t in zip(shape, tile)]
    if sweep_axis is None:
        per_tile = prod(t + lo + hi for t, (lo, hi) in zip(tile, halo))
        return prod(ntiles) * per_tile * dtype_bytes
    s = sweep_axis
    cross = prod(
        t + lo + hi
        for i, (t, (lo, hi)) in enumerate(zip(tile, halo))
        if i != s
    )
    ncols = prod(nt for i, nt in enumerate(ntiles) if i != s)
    swept = ntiles[s] * tile[s] + halo[s][0] + halo[s][1]
    return ncols * swept * cross * dtype_bytes


def tile_vmem_bytes(
    tile: Sequence[int],
    halo: Sequence[tuple[int, int]],
    dtype_bytes: int,
    sweep_axis: int | None = None,
    prefetch: bool = True,
    time_steps: int = 1,
    stage_halos: Sequence[Sequence[tuple[int, int]]] | None = None,
    aligned: bool = False,
) -> int:
    """Per-operand VMEM footprint: the halo'd window, plus — when sweeping
    with prefetch — two landing slabs for the double-buffered next-tile DMA.
    ``aligned`` charges the window as :func:`window_extents` rounds it for
    the chip's DMA grain.

    With ``time_steps=T > 1`` the window (and slabs) carry the T×-grown
    halo; ``stage_halos`` carries a heterogeneous chain's summed halo
    instead.  The T−1 staged trapezoid buffers are *not* included here:
    the kernel allocates one shared set per launch, not one per operand,
    so they are priced by :func:`fused_stage_bytes` and charged once
    against the whole budget in :func:`select_tile` — folding them into
    the per-operand figure would reserve them ``n_operands`` times.
    """
    full = (
        chain_halo(stage_halos)
        if stage_halos is not None
        else fused_halo(halo, time_steps)
    )
    ext = window_extents(tile, full, dtype_bytes, aligned)
    window = prod(ext)
    slabs = 0
    if sweep_axis is not None and prefetch:
        cross = prod(e for i, e in enumerate(ext) if i != sweep_axis)
        slabs = 2 * tile[sweep_axis] * cross
    return (window + slabs) * dtype_bytes


def fused_stage_bytes(
    tile: Sequence[int],
    halo: Sequence[tuple[int, int]],
    dtype_bytes: int,
    time_steps: int,
    stage_halos: Sequence[Sequence[tuple[int, int]]] | None = None,
    window_kind: str = "trapezoid",
    sweep_axis: int | None = None,
    stage_dtype_bytes: Sequence[int] | None = None,
    aligned: bool = False,
) -> int:
    """Bytes of the T−1 staged intermediates, shared per launch.

    ``window_kind="trapezoid"``: stage j (1 ≤ j < T) holds
    ``T_i + (T−j)(h_lo_i + h_hi_i)`` per dim — the full warm-up cone.
    With ``stage_halos`` stage j holds ``T_i +`` the suffix sum of stages
    ``j+1..T``'s halos instead (``halo``/``time_steps`` ignored).

    ``window_kind="ring"`` (DESIGN.md §14): along ``sweep_axis`` the
    frontier feeding stage j only keeps the steady-state band stage j's
    streaming read consumes — ``T_s + h_lo_j_s + h_hi_j_s`` rows (that
    stage's *own* sweep halo, not the suffix sum) — so the resident set
    stops growing with the remaining chain depth.  Cross axes keep the
    suffix extents (they do not stream).  ``sweep_axis=None`` has no
    stream to renormalize along, so it prices the trapezoid.

    ``stage_dtype_bytes[j]`` sizes the frontier holding stage j's output
    (0-indexed; default ``dtype_bytes`` for every stage).  ``aligned``
    charges each buffer at its allocated size: the chip pads the last two
    axes to the frontier dtype's (sublane, lane) grain."""
    if window_kind not in WINDOW_KINDS:
        raise ValueError(
            f"window_kind {window_kind!r} not in {WINDOW_KINDS}"
        )
    if stage_halos is None:
        stage_halos = [list(halo)] * max(int(time_steps), 1)
    T = len(stage_halos)
    if stage_dtype_bytes is None:
        stage_dtype_bytes = [dtype_bytes] * T
    suffix = stage_suffix_halos(stage_halos)
    total = 0
    for j in range(1, T):
        ext = [t + lo + hi for t, (lo, hi) in zip(tile, suffix[j - 1])]
        if window_kind == "ring" and sweep_axis is not None:
            s = sweep_axis
            ext[s] = (
                tile[s] + stage_halos[j][s][0] + stage_halos[j][s][1]
            )
        sdb = int(stage_dtype_bytes[j - 1])
        ext = window_extents(ext, [(0, 0)] * len(ext), sdb, aligned)
        total += sdb * prod(ext)
    return total


def _allocated_bytes(shape: Sequence[int], dtype_bytes: int) -> int:
    """Bytes of a VMEM buffer as allocated: the last two axes padded to
    the dtype's (sublane, lane) grain."""
    ext = window_extents(shape, [(0, 0)] * len(shape), dtype_bytes)
    return prod(ext) * int(dtype_bytes)


def kernel_vmem_bytes(
    tile: Sequence[int],
    halo: Sequence[tuple[int, int]],
    dtype_bytes: int,
    sweep_axis: int | None = None,
    prefetch: bool = True,
    n_inputs: int = 1,
    time_steps: int = 1,
    stage_halos: Sequence[Sequence[tuple[int, int]]] | None = None,
    window_kind: str = "trapezoid",
    stage_dtype_bytes: Sequence[int] | None = None,
) -> int:
    """Scoped VMEM one compiled launch of the sweep kernel holds, all as
    allocated: each input's window and prefetch slabs, the staged
    frontiers, the double-buffered output block, the body's f32 values —
    the window cast to f32 (twice: Mosaic keeps relayout copies of
    unaligned slices), and a tap slice, the accumulator, a mask and a
    stored copy per stage extent — and the compiler's slack.

    Calibrated against the v5e compiler's smallest accepted
    ``vmem_limit_bytes`` for the 512^3 single application, the 256^3
    fused f32 and bf16 rings, the 16384^2 Jacobi and the 4-shard 512^3
    launches (6-64 MiB needed; this model gives more in every case).  The
    launcher passes it as the limit, and the planner rejects tiles whose
    figure exceeds the target core's VMEM, so a planned launch fits."""
    s = 0 if sweep_axis is None else sweep_axis  # the kernel's grid order
    if stage_halos is None:
        stage_halos = [list(halo)] * max(int(time_steps), 1)
    depth = len(stage_halos)
    if stage_dtype_bytes is None:
        stage_dtype_bytes = [dtype_bytes] * depth
    window = window_extents(tile, chain_halo(stage_halos), dtype_bytes)
    total = n_inputs * tile_vmem_bytes(
        tile, halo, dtype_bytes, s, prefetch, stage_halos=stage_halos,
        aligned=True,
    )
    if depth > 1:
        total += fused_stage_bytes(
            tile, halo, dtype_bytes, depth, stage_halos=stage_halos,
            window_kind=window_kind, sweep_axis=s,
            stage_dtype_bytes=stage_dtype_bytes, aligned=True,
        )
    total += 2 * _allocated_bytes(tile, stage_dtype_bytes[-1])
    exts = [
        [t + lo + hi for t, (lo, hi) in zip(tile, sfx)]
        for sfx in stage_suffix_halos(stage_halos)
    ]
    total += 2 * _allocated_bytes(window, 4)
    total += 4 * sum(_allocated_bytes(e, 4) for e in exts)
    return total + KERNEL_VMEM_SLACK


def chain_flops(
    shape: Sequence[int],
    tile: Sequence[int],
    stage_points: Sequence[int],
    stage_halos: Sequence[Sequence[tuple[int, int]]],
    sweep_axis: int | None = None,
    streaming: bool = True,
) -> int:
    """Modeled multiply-add flops of one fused launch over the whole grid.

    ``stage_points[j]`` is the number of stencil points of stage j (each
    output element costs ``2·s_j`` flops — one multiply and one add per
    point).  Stage j's computed extent is ``tile + suffix_j`` per dim
    (:func:`stage_suffix_halos`); the final stage computes the bare tile.

    ``streaming=False`` is the §8 recompute trapezoid: every sweep step
    recomputes each stage's full extent.  ``streaming=True`` is the §9
    frontier kernel: the first step of each sweep column computes the full
    extents (warm-up), every later step only the ``T_s`` newly-uncovered
    rows per stage (cross extents unchanged).  With ``sweep_axis=None``
    there is no sweep to stream along, so both modes price the full
    per-tile trapezoid.
    """
    shape = tuple(int(n) for n in shape)
    tile = tuple(int(t) for t in tile)
    suffix = stage_suffix_halos(stage_halos)
    ntiles = [-(-n // t) for n, t in zip(shape, tile)]
    flops = 0
    for j, s_j in enumerate(stage_points):
        ext = tuple(t + lo + hi for t, (lo, hi) in zip(tile, suffix[j]))
        full = prod(ext)
        if sweep_axis is None:
            per_region = prod(ntiles) * full
        else:
            ncols = prod(nt for i, nt in enumerate(ntiles) if i != sweep_axis)
            nswp = ntiles[sweep_axis]
            if streaming:
                cross = prod(e for i, e in enumerate(ext) if i != sweep_axis)
                per_col = full + (nswp - 1) * tile[sweep_axis] * cross
            else:
                per_col = nswp * full
            per_region = ncols * per_col
        flops += 2 * int(s_j) * per_region
    return flops


def select_tile(
    shape: Sequence[int],
    halo: Sequence[tuple[int, int]],
    dtype_bytes: int = 4,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    n_operands: int = 2,
    sweep_axis: int | None | str = "auto",
    aligned: bool = True,
    prefetch: bool = True,
    extra_tiles: Sequence[Sequence[int]] | None = None,
    time_steps: int = 1,
    stage_halos: Sequence[Sequence[tuple[int, int]]] | None = None,
    exclude_sweep_axis: int | None = None,
    window_kind: str = "trapezoid",
    stage_dtype_bytes: Sequence[int] | None = None,
) -> TileChoice:
    """Pick the traffic-minimizing VMEM tile (paper §4 adapted, §5 for the
    per-operand budget split: budget/n_operands per array).

    ``sweep_axis``: ``"auto"`` tries every axis with halo reuse (and the
    per-tile-halo fallback) and keeps the cheapest; an int forces that
    sweep axis; ``None`` forces the seed's per-tile-halo model.

    ``exclude_sweep_axis`` (the §10 shard axis) removes one axis from the
    ``"auto"`` enumeration — a shard sweeps within its own column slab,
    never along the partitioned axis.  Excluding axis 0 also drops the
    per-tile-halo fallback: the engine realizes ``sweep_axis=None`` as
    axis-0 grid order, which would collide with the shard partition.

    ``extra_tiles``: additional candidate tiles scored alongside the
    default enumeration under every sweep axis — the plan compiler feeds
    the reduced-basis box and the s2v-optimal box through this hook, so
    its result can only improve on the bare heuristic.

    ``time_steps=T > 1`` scores one *fused* pass — T applications per HBM
    sweep — with the T×-grown halos in the traffic model and the staged
    intermediate windows charged against the budget.  The returned
    ``traffic_bytes`` pays for all T applications of that launch.
    ``stage_halos`` scores a heterogeneous stage-chain launch instead
    (per-stage halos summed for the window, suffix-summed for the staged
    buffers); ``halo`` is then only the per-application union used for
    the surface-to-volume diagnostic and the lower-bound radius.

    ``window_kind="ring"`` sizes the staged intermediates as steady-state
    rings along the chosen sweep axis instead of full trapezoids —
    traffic is unchanged, but deeper fusion stays feasible at the same
    budget.  ``stage_dtype_bytes`` sizes each staged buffer at its own
    stage's element width (mixed-precision chains); the input windows are
    still priced at ``dtype_bytes``.
    """
    shape = tuple(int(n) for n in shape)
    halo = [(int(lo), int(hi)) for lo, hi in halo]
    if stage_halos is not None:
        stage_halos = [
            [(int(lo), int(hi)) for lo, hi in h] for h in stage_halos
        ]
    budget = vmem_budget // max(n_operands, 1)
    max_elems = budget // dtype_bytes
    extras = [
        tuple(int(t) for t in e)
        for e in (extra_tiles or [])
        if len(e) == len(shape) and all(1 <= int(t) for t in e)
    ]
    if sweep_axis == "auto":
        axes: list[int | None] = [None] + [
            i for i, n in enumerate(shape) if n > 1
        ]
        if exclude_sweep_axis is not None:
            axes = [
                s for s in axes
                if s != exclude_sweep_axis
                and not (s is None and exclude_sweep_axis == 0)
            ]
    else:
        axes = [sweep_axis]
    # The radius fed to the lower bound must dominate the halo: an
    # asymmetric halo like conv1d's (W-1, 0) has radius max(lo, hi), NOT
    # (lo+hi)//2 (integer floor under-estimates it).
    r = max(max(lo, hi) for lo, hi in halo)
    # One isoperimetric bound per launch: a fused launch is still a single
    # sweep of the grid (with a radius-T·r dependency cone), and the Eq. 7
    # bound is monotone in the radius, so the single-sweep bound stays a
    # valid — conservative — floor under the fused traffic model.
    lb = _traffic_lower_bound(shape, budget // dtype_bytes, dtype_bytes, r)
    time_steps = max(int(time_steps), 1)
    depth = len(stage_halos) if stage_halos is not None else time_steps
    best: TileChoice | None = None
    for axis in axes:
        cands = candidate_tiles(shape, max_elems, axis, aligned, dtype_bytes)
        if extras:
            seen = set(cands)
            cands = cands + [t for t in extras if t not in seen]
        for tile in cands:
            vmem = tile_vmem_bytes(
                tile, halo, dtype_bytes, axis, prefetch, time_steps,
                stage_halos=stage_halos, aligned=aligned,
            )
            if vmem > budget:
                continue
            if depth > 1:
                # The staged frontier buffers are one shared set per
                # launch — charge them against the whole budget on top of
                # the per-operand windows, not inside each operand's share.
                stages = fused_stage_bytes(
                    tile, halo, dtype_bytes, time_steps,
                    stage_halos=stage_halos,
                    window_kind=window_kind,
                    sweep_axis=axis,
                    stage_dtype_bytes=stage_dtype_bytes,
                    aligned=aligned,
                )
                if vmem * max(n_operands, 1) + stages > vmem_budget:
                    continue
            if aligned and kernel_vmem_bytes(
                tile, halo, dtype_bytes, axis, prefetch,
                max(n_operands - 1, 1), time_steps, stage_halos,
                window_kind, stage_dtype_bytes,
            ) > TARGET_VMEM_BYTES:
                continue  # the compiled kernel would not fit the core
            traffic = tile_traffic_bytes(
                shape, tile, halo, dtype_bytes, axis, time_steps,
                stage_halos=stage_halos,
            )
            if best is not None and traffic >= best.traffic_bytes:
                continue
            eff = lb / traffic if traffic else 1.0
            assert eff <= 1.0 + 1e-9, (
                f"traffic model below isoperimetric bound: tile={tile} "
                f"axis={axis} traffic={traffic} lb={lb}"
            )
            best = TileChoice(
                tile=tile,
                grid=tuple(-(-n // t) for n, t in zip(shape, tile)),
                traffic_bytes=traffic,
                vmem_bytes=vmem,
                surface_to_volume=surface_to_volume(tile, halo),
                lower_bound_bytes=lb,
                efficiency=min(eff, 1.0),
                sweep_axis=axis,
            )
    if best is None:
        constraint = (
            f" with the sweep constrained off shard axis {exclude_sweep_axis}"
            if exclude_sweep_axis is not None
            else ""
        )
        raise ValueError(
            f"no tile of {shape} (halo {halo}) fits VMEM budget {budget} B"
            + constraint
        )
    return best


def _traffic_lower_bound(
    shape: tuple[int, ...], vmem_words: int, dtype_bytes: int, r: int
) -> float:
    """Isoperimetric lower bound on bytes moved (Eq. 7 with S = VMEM words).

    Collapse degenerate dims (extent 1) — the bound is dimensional.
    """
    eff = [n for n in shape if n > 1]
    if len(eff) < 2 or r == 0:
        return prod(shape) * dtype_bytes  # compulsory traffic only
    lb = lower_bound_loads(eff, vmem_words, p=1)
    return max(lb["bound"], lb["compulsory"]) * dtype_bytes
