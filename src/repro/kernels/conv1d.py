"""Sweep-pipelined Pallas depthwise causal conv1d — the Mamba2 stencil.

A width-W causal depthwise convolution is the 1-D instantiation of the
sweep engine in ``kernels.stencil``: a stencil with the asymmetric halo
(W-1, 0) on the sequence axis.  The sequence is swept in tiles of
``tile_s`` tokens per batch row; the W-1-token overlap between consecutive
tiles is shifted inside VMEM (DESIGN.md §4) instead of re-fetched, and the
next slab is prefetched into a double buffer while the current tile
computes.  Channels ride whole in the lane dimension.

Matches ``models.ssm._causal_conv`` (causal, silu-activated); the optional
``state`` argument supplies the previous sequence's W-1-token tail so the
kernel drops into the serving path's chunked prefill.  A custom VJP backs
the kernel with the reference gradient, so it is safe under ``jax.grad``
(training uses it when ``SSMCfg.pallas_conv`` is set).

**Mixed precision (DESIGN.md §14).**  The kernel is dtype-preserving end
to end: a bf16 input keeps its VMEM window, prefetch slabs, and output in
bf16 (half the window bytes, double the sublane grain — the same
dtype-aware tiling the stencil engine's ring windows use), while every
multiply-accumulate, the bias add, and the silu run in f32 exactly as on
the f32 path.  The custom VJP recomputes its pre-activation in f32 too,
so gradients differ from the f32 path only by the bf16 rounding of the
inputs/outputs themselves — the tolerance the parity test pins.  The
planned ``tile_s`` prices the window at the *input's* element width, so
bf16 calls legally plan longer sweep tiles under the same VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.tiling import kernel_vmem_bytes, window_extents
from ._backend import checked_vmem_limit, resolve_interpret

__all__ = ["causal_conv1d"]


def _window_rows(tile_s, halo, channels, itemsize):
    """Sequence rows of the VMEM window: ``tile_s + halo`` rounded up to
    the sublane grain every DMA on the chip moves whole (the stencil
    engine's ``window_extents``; the trailing slack is never read)."""
    return window_extents(
        (tile_s, channels), [(halo, 0), (0, 0)], itemsize
    )[0]


@functools.partial(jax.jit, static_argnames=("tile_s", "interpret"))
def _conv_call(xp, conv_w, conv_b, tile_s, interpret):
    """xp: (B, halo + padded S + slack, C) — halo rows prepended, grain
    slack appended (:func:`_prepend_halo`).  Sweeps tiles of ``tile_s``
    tokens with halo reuse + double-buffered prefetch."""
    b, sp, c = xp.shape
    width = conv_w.shape[0]
    halo = width - 1
    rows = _window_rows(tile_s, halo, c, xp.dtype.itemsize)
    keep = rows - tile_s  # rows each sweep step carries over
    pad_s = sp - rows + tile_s
    nswp = pad_s // tile_s
    pipelined = nswp > 1 and halo > 0

    def body(*refs):
        if pipelined:
            x_hbm, w_ref, b_ref, o_ref, win, slab, wsem, ssem = refs
        else:
            x_hbm, w_ref, b_ref, o_ref, win, wsem = refs
        i = pl.program_id(0)  # batch row
        k = pl.program_id(1)  # sweep step (minor-most: fastest-varying)

        def slab_copy(kk, slot):
            return pltpu.make_async_copy(
                x_hbm.at[i, pl.ds(kk * tile_s + keep, tile_s)],
                slab.at[slot],
                ssem.at[slot],
            )

        if not pipelined:
            cp = pltpu.make_async_copy(
                x_hbm.at[i, pl.ds(k * tile_s, rows)], win, wsem
            )
            cp.start()
            cp.wait()
        else:
            @pl.when(k == 0)
            def _():
                cp = pltpu.make_async_copy(
                    x_hbm.at[i, pl.ds(0, rows)], win, wsem
                )
                cp.start()
                slab_copy(1, 1 % 2).start()
                cp.wait()

            @pl.when(k > 0)
            def _():
                win[0:keep, :] = win[tile_s:rows, :]
                slab_copy(k, k % 2).wait()

                @pl.when(k + 1 < nswp)
                def _():
                    slab_copy(k + 1, (k + 1) % 2).start()
                win[keep:rows, :] = slab[k % 2]

        acc = jnp.zeros((tile_s, c), jnp.float32)
        for t in range(width):
            acc = acc + win[t : t + tile_s, :].astype(jnp.float32) * w_ref[t]
        acc = acc + b_ref[...]
        o_ref[...] = jax.nn.silu(acc).astype(o_ref.dtype)[None]

    scratch = [pltpu.VMEM((rows, c), xp.dtype)]
    if pipelined:
        scratch.append(pltpu.VMEM((2, tile_s, c), xp.dtype))
    scratch.append(pltpu.SemaphoreType.DMA)
    if pipelined:
        scratch.append(pltpu.SemaphoreType.DMA((2,)))

    # The stencil engine's kernel model: a (tile_s, C) tile with halo
    # (W-1, 0) on the swept sequence axis (the weight and bias blocks fit
    # in its slack).
    vmem_limit = None if interpret else checked_vmem_limit(
        kernel_vmem_bytes(
            (tile_s, c), [(halo, 0), (0, 0)], xp.dtype.itemsize, 0,
            pipelined,
        )
    )
    out = pl.pallas_call(
        body,
        grid=(b, nswp),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((width, c), lambda i, k: (0, 0)),
            pl.BlockSpec((c,), lambda i, k: (0,)),
        ],
        out_specs=pl.BlockSpec((1, tile_s, c), lambda i, k: (i, k, 0)),
        out_shape=jax.ShapeDtypeStruct((b, pad_s, c), xp.dtype),
        scratch_shapes=scratch,
        compiler_params=(
            None if interpret
            else pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)
        ),
        interpret=interpret,
        name="conv1d_sweep",
    )(xp, conv_w, conv_b)
    return out


def _prepend_halo(x, conv_w, state, tile_s):
    """Concat the W-1 halo (zeros or the previous tail), round S up to
    whole tiles and append the last window's grain slack."""
    b, s, c = x.shape
    width = conv_w.shape[0]
    halo = width - 1
    tile_s = min(tile_s, s)
    pad_s = -(-s // tile_s) * tile_s
    slack = _window_rows(tile_s, halo, c, x.dtype.itemsize) - tile_s - halo
    if state is None:
        head = jnp.zeros((b, halo, c), x.dtype)
    else:
        head = state.astype(x.dtype)
    xp = jnp.concatenate(
        [head, x, jnp.zeros((b, pad_s - s + slack, c), x.dtype)], axis=1
    )
    return xp, tile_s


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_grad(x, conv_w, conv_b, tile_s, interpret):
    xp, tile_s = _prepend_halo(x, conv_w, None, tile_s)
    return _conv_call(xp, conv_w, conv_b, tile_s, interpret)[:, : x.shape[1]]


def _conv_grad_fwd(x, conv_w, conv_b, tile_s, interpret):
    return _conv_grad(x, conv_w, conv_b, tile_s, interpret), (x, conv_w, conv_b)


def _conv_grad_bwd(tile_s, interpret, res, g):
    # Reference-math backward: recompute the pre-activation, silu', then the
    # transposed (anti-causal) correlation.  out[t] = silu(Σ_i full[t+i] w_i)
    # with full = [0^(W-1), x], so x[u] feeds out[u-(W-1)+i·] ⇒ the grad is
    # the same stencil with flipped offsets.
    x, conv_w, conv_b = res
    b, s, c = x.shape
    width = conv_w.shape[0]
    halo = width - 1
    full = jnp.concatenate([jnp.zeros((b, halo, c), x.dtype), x], axis=1)
    pre = jnp.zeros((b, s, c), jnp.float32)
    for i in range(width):
        pre = pre + full[:, i : i + s, :].astype(jnp.float32) * conv_w[i]
    pre = pre + conv_b
    sig = jax.nn.sigmoid(pre)
    gpre = g.astype(jnp.float32) * sig * (1.0 + pre * (1.0 - sig))
    gp = jnp.concatenate([gpre, jnp.zeros((b, halo, c), gpre.dtype)], axis=1)
    dx = jnp.zeros((b, s, c), jnp.float32)
    for i in range(width):
        dx = dx + gp[:, halo - i : halo - i + s, :] * conv_w[i]
    dw = jnp.stack(
        [
            jnp.einsum("btc,btc->c", gpre, full[:, i : i + s, :].astype(jnp.float32))
            for i in range(width)
        ]
    )
    db = gpre.sum(axis=(0, 1))
    return dx.astype(x.dtype), dw.astype(conv_w.dtype), db.astype(conv_b.dtype)


_conv_grad.defvjp(_conv_grad_fwd, _conv_grad_bwd)


@functools.lru_cache(maxsize=512)
def _planned_tile_s(seq: int, channels: int, width: int, dtype_bytes: int) -> int:
    """Sweep-tile length from the plan compiler: the conv is a (S, C) grid
    with halo (W-1, 0) on the swept sequence axis.  The planner's
    persistent cache (plus this per-process memo) makes the serving-path
    repeat O(1)."""
    from repro.plan import default_planner

    offs = tuple((-i, 0) for i in range(width))
    plan = default_planner().plan(
        shape=(seq, channels), offsets=(offs,), dtype_bytes=dtype_bytes,
        n_operands=2,
    )
    # The plan's sweep tile when it sweeps the sequence axis; otherwise the
    # whole (budget-clamped) sequence is one tile and there is no sweep.
    return int(plan.tile[0])


def causal_conv1d(
    x: jnp.ndarray,
    conv_w: jnp.ndarray,
    conv_b: jnp.ndarray,
    tile_s: int | None = None,
    interpret: bool | None = None,
    state: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """x: (B, S, C); conv_w: (W, C); conv_b: (C,).  Causal, silu-activated
    (matches models.ssm._causal_conv).  ``state``: optional (B, W-1, C)
    tail of the previous sequence used as the leading halo (serving path;
    not differentiated).  ``tile_s=None`` asks the plan compiler for the
    traffic-minimizing sweep tile."""
    interpret = resolve_interpret(interpret, kernel="conv1d")
    if tile_s is None:
        tile_s = _planned_tile_s(
            int(x.shape[1]), int(x.shape[2]), int(conv_w.shape[0]),
            x.dtype.itemsize,
        )
    if state is None:
        return _conv_grad(x, conv_w, conv_b, int(tile_s), bool(interpret))
    xp, tile_s = _prepend_halo(x, conv_w, state, tile_s)
    return _conv_call(xp, conv_w, conv_b, tile_s, bool(interpret))[
        :, : x.shape[1]
    ]
