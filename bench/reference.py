"""The plain reference: a configuration's operator applied in
``jax.numpy`` f32, block by block.

It reads the operator (offsets and weights), the boundary and the number
of applications from the configuration and traffic files, and nothing
from the program under test.  It works on row blocks of axis 0, each
assembled on one device from the arrays' own shards, so that it fits
beside the arrays it checks at the timed sizes, on one chip or four.

Boundaries: ``zero`` reads 0 outside the grid before every application;
``periodic`` wraps every axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["BLOCK_BYTES", "apply", "compare"]

# Largest row block of the input assembled on one device at a time.
BLOCK_BYTES = 256 << 20


def _halo(offsets):
    offs = np.asarray(offsets, dtype=np.int64)
    return [(int(max(0, -offs[:, i].min())), int(max(0, offs[:, i].max())))
            for i in range(offs.shape[1])]


def _block_rows(shape, itemsize):
    """Rows per block: all of axis 0 if it fits in ``BLOCK_BYTES``, else
    the largest divisor of it that does."""
    n = int(shape[0])
    row = itemsize * int(np.prod(shape[1:]))
    if n * row <= BLOCK_BYTES:
        return n
    best = 1
    for b in range(1, n + 1):
        if n % b == 0 and b * row <= BLOCK_BYTES:
            best = b
    return best


def _rows(arr, lo, hi, device):
    """Global rows ``[lo, hi)`` of axis 0 of ``arr`` (``0 <= lo < hi <=
    n``) as one array on ``device``, put together from its shards."""
    shape = arr.shape
    parts, seen = [], set()
    for sh in arr.addressable_shards:
        idx = tuple(
            (s.start or 0, s.stop if s.stop is not None else n)
            for s, n in zip(sh.index, shape)
        )
        if idx in seen:
            continue
        seen.add(idx)
        a, b = idx[0]
        a2, b2 = max(a, lo), min(b, hi)
        if a2 >= b2:
            continue
        piece = jax.device_put(sh.data[a2 - a:b2 - a], device)
        parts.append(((a2 - lo,) + tuple(s for s, _ in idx[1:]), piece))
    full = (hi - lo,) + tuple(shape[1:])
    if len(parts) == 1 and parts[0][1].shape == full:
        return parts[0][1]
    buf = jax.device_put(jnp.zeros(full, arr.dtype), device)
    for pos, piece in parts:
        buf = jax.lax.dynamic_update_slice(buf, piece, pos)
    return buf


def _slab(arr, lo, hi, boundary, device):
    """Rows ``[lo, hi)`` of axis 0, which may reach past the grid: zeros
    there for a zero boundary, the wrapped rows for a periodic one."""
    n = arr.shape[0]
    rest = tuple(arr.shape[1:])
    pieces = []
    r = lo
    while r < hi:
        if 0 <= r < n:
            end = min(hi, n)
            pieces.append(_rows(arr, r, end, device))
        else:
            end = min(hi, 0) if r < 0 else hi
            if boundary == "periodic":
                w = r % n
                pieces.append(_rows(arr, w, w + end - r, device))
            else:
                pieces.append(jax.device_put(
                    jnp.zeros((end - r,) + rest, arr.dtype), device
                ))
        r = end
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, 0)


@functools.partial(jax.jit, static_argnames=("offsets", "weights", "steps",
                                             "boundary", "n0", "halo"))
def _apply_block(slab, row0, *, offsets, weights, steps, boundary, n0, halo):
    """``steps`` applications to a row slab whose first row is global row
    ``row0``; each application drops ``halo[0]`` rows at either end."""
    d = slab.ndim
    mode = "wrap" if boundary == "periodic" else "constant"
    v = slab.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            lo0, hi0 = halo[0]
            rows = v.shape[0] - lo0 - hi0
            vp = jnp.pad(v, [(0, 0)] + list(halo[1:]), mode=mode)
            acc = jnp.zeros((rows,) + v.shape[1:], jnp.float32)
            for off, w in zip(offsets, weights):
                sl = [slice(lo0 + off[0], lo0 + off[0] + rows)]
                for i in range(1, d):
                    lo = halo[i][0]
                    sl.append(slice(lo + off[i], lo + off[i] + v.shape[i]))
                acc = acc + jnp.float32(w) * vp[tuple(sl)]
            row0 = row0 + lo0
            if boundary != "periodic":
                g = row0 + jnp.arange(rows)
                inside = (g >= 0) & (g < n0)
                acc = jnp.where(
                    inside.reshape((rows,) + (1,) * (d - 1)), acc, 0.0
                )
            v = acc
    return v


@jax.jit
def _gaps(out_block, ref_block, in_block):
    return (jnp.max(jnp.abs(out_block.astype(jnp.float32) - ref_block)),
            jnp.max(jnp.abs(in_block.astype(jnp.float32))))


def compare(inp, out, offsets, weights, steps, boundary):
    """``(max |out - ref|, max |inp|)``, with ``ref`` the operator applied
    ``steps`` times to ``inp`` in f32."""
    if boundary not in ("zero", "periodic"):
        raise ValueError(f"reference knows no boundary {boundary!r}")
    offsets = tuple(tuple(int(x) for x in o) for o in offsets)
    weights = tuple(float(w) for w in weights)
    halo = _halo(offsets)
    reach = [(lo * steps, hi * steps) for lo, hi in halo]
    n = inp.shape[0]
    b = _block_rows(inp.shape, 4)
    # Each block is checked on the device that holds its first output row,
    # so the work spreads over the chips of an output split along axis 0.
    out_devices = {}
    for sh in out.addressable_shards:
        out_devices.setdefault(sh.index[0].start or 0, sh.device)
    gap = big = 0.0
    for r0 in range(0, n, b):
        dev = out_devices[max(k for k in out_devices if k <= r0)]
        slab = _slab(inp, r0 - reach[0][0], r0 + b + reach[0][1], boundary,
                     dev)
        ref = _apply_block(
            slab, jnp.int32(r0 - reach[0][0]), offsets=offsets,
            weights=weights, steps=int(steps), boundary=boundary, n0=n,
            halo=tuple(halo),
        )
        g, m = _gaps(_rows(out, r0, r0 + b, dev), ref,
                     _rows(inp, r0, r0 + b, dev))
        gap = max(gap, float(g))
        big = max(big, float(m))
    return gap, big


@functools.partial(jax.jit, static_argnames=("offsets", "weights", "steps",
                                             "boundary", "dtype"))
def _apply(u, *, offsets, weights, steps, boundary, dtype):
    d = u.ndim
    halo = _halo(offsets)
    mode = "wrap" if boundary == "periodic" else "constant"
    v = u.astype(dtype)
    for _ in range(steps):
        vp = jnp.pad(v, halo, mode=mode)
        acc = jnp.zeros(v.shape, dtype)
        for off, w in zip(offsets, weights):
            sl = tuple(slice(halo[i][0] + off[i], halo[i][0] + off[i] +
                             v.shape[i]) for i in range(d))
            acc = acc + jnp.asarray(w, dtype) * vp[sl]
        v = acc
    return v


def apply(u, offsets, weights, steps, boundary, dtype="float32"):
    """The operator applied ``steps`` times to the whole of ``u``, every
    value and product held at ``dtype``: the control puts this, at a
    precision below the configuration's, in the program's place."""
    if boundary not in ("zero", "periodic"):
        raise ValueError(f"reference knows no boundary {boundary!r}")
    return _apply(
        u, offsets=tuple(tuple(int(x) for x in o) for o in offsets),
        weights=tuple(float(w) for w in weights), steps=int(steps),
        boundary=boundary, dtype=jnp.dtype(dtype),
    )
