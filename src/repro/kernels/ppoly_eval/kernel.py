"""Pallas TPU kernel: batched piecewise-polynomial evaluation.

BottleMod's hot loop when used online (Sect. 6 / Sect. 8: "repeatedly executed
with updated state from monitoring") is evaluating *many* piecewise functions
(progress, resource usage, buffered data of every process; every candidate
allocation of a what-if sweep à la Fig. 7) at *many* time points.

TPU adaptation: a data-dependent binary search per query is
VPU-hostile, so each (function-tile × query-tile) block holds the whole
breakpoint/coefficient table in VMEM and selects pieces with a vectorized
compare-reduce (``idx = Σ (start ≤ t) − 1``) followed by a one-hot masked
Horner evaluation — O(P·K) lane-parallel FLOPs per query, no gathers, no
scalar loops.  The MXU is not involved; this is a pure VPU kernel, and block
shapes keep the last dimension at 128 lanes.

The kernels are float32 and are built with ``jax_enable_x64`` off (scoped to
the ``pallas_call``): the sweep engine turns x64 on for the process, and the
TPU compiler rejects the 64-bit constants and grid indices it would produce.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import _BIG, PAD_START


def _ppoly_kernel(starts_ref, q_ref, *refs, n_pieces: int):
    *coef_refs, out_ref = refs
    out_ref[...] = _eval_one(starts_ref[...], [c[...] for c in coef_refs],
                             q_ref[...], n_pieces)


def _eval_one(starts, planes, q, n_pieces: int):
    """Shared kernel body: evaluate (bB, P) pieces at (bB, bT) queries.

    ``planes`` holds the K coefficient planes as separate (bB, P) arrays:
    Mosaic lowers a lane slice of a (bB, P, K) block to a gather, which the
    TPU compiler refuses, so the planes are split before the kernel.
    """
    cmp = (starts[:, None, :] <= q[:, :, None]).astype(jnp.float32)   # (bB,bT,P)
    idx = jnp.maximum(jnp.sum(cmp, axis=-1) - 1.0, 0.0)               # (bB,bT)
    piece_ids = jax.lax.broadcasted_iota(          # Mosaic iota is int-only
        jnp.int32, (1, 1, n_pieces), 2).astype(jnp.float32)
    onehot = (idx[:, :, None] == piece_ids).astype(jnp.float32)       # (bB,bT,P)
    u = (q[:, :, None] - starts[:, None, :]) * onehot
    acc = jnp.zeros_like(u)
    for c in reversed(planes):
        acc = acc * u + c[:, None, :]
    return jnp.sum(acc * onehot, axis=-1)


_PAD_HALF = PAD_START * 0.5  # padding-slot detection threshold


def _ppoly_min_kernel(starts_ref, q_ref, *refs, n_fns: int, n_pieces: int):
    """min over F stacked functions with argmin; F is a static Python loop."""
    *coef_refs, val_ref, arg_ref = refs
    q = q_ref[...]                                      # (bB, bT)
    best = jnp.full_like(q, _BIG)
    arg = jnp.zeros_like(q)
    for f in range(n_fns):
        starts_f = starts_ref[:, f, :]                  # (bB, P)
        planes_f = [c[:, f, :] for c in coef_refs]      # K x (bB, P)
        v = _eval_one(starts_f, planes_f, q, n_pieces)
        valid = (starts_f[:, 0] < _PAD_HALF)[:, None]   # padding function slot?
        v = jnp.where(valid, v, _BIG)
        take = v < best                                 # strict: ties keep lowest f
        arg = jnp.where(take, jnp.float32(f), arg)
        best = jnp.where(take, v, best)
    val_ref[...] = best
    arg_ref[...] = arg


def ppoly_min_eval_pallas(starts: jnp.ndarray, coeffs: jnp.ndarray, q: jnp.ndarray,
                          *, block_b: int = 8, block_t: int = 128,
                          interpret: bool = True):
    """``pallas_call`` wrapper for min-with-argmin over stacked functions.

    starts (B, F, P) · coeffs (B, F, P, K) · q (B, T) → ((B, T), (B, T)).
    The argmin output is float32 (lane-friendly); cast at the call site.
    """
    B, F, P = starts.shape
    K = coeffs.shape[-1]
    T = q.shape[-1]
    assert B % block_b == 0 and T % block_t == 0, "pad inputs to block multiples"
    grid = (B // block_b, T // block_t)
    kernel = functools.partial(_ppoly_min_kernel, n_fns=F, n_pieces=P)
    fn_spec = pl.BlockSpec((block_b, F, P), lambda i, j: (i, 0, 0))
    q_spec = pl.BlockSpec((block_b, block_t), lambda i, j: (i, j))
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[fn_spec, q_spec] + [fn_spec] * K,
            out_specs=[q_spec, q_spec],
            out_shape=[jax.ShapeDtypeStruct((B, T), jnp.float32)] * 2,
            interpret=interpret,
        )(starts, q, *(coeffs[..., k] for k in range(K)))


def _first_crossing_kernel(starts_ref, c0_ref, c1_ref, c2_ref, plen_ref,
                           y_ref, out_ref):
    """First t with f(t) >= y for monotone piecewise f, degree <= 2.

    Same VPU shape as the eval kernel: the whole piece table sits in VMEM
    and every (query × piece) candidate is computed lane-parallel — the
    quadratic branch adds a handful of element-wise FLOPs (the stable
    q-branch roots), no gathers, no data-dependent control flow.
    """
    from .ref import first_crossing_candidates

    starts = starts_ref[...]            # (bB, P)
    c0 = c0_ref[...]                    # (bB, P)
    c1 = c1_ref[...]                    # (bB, P)
    c2 = c2_ref[...]                    # (bB, P)
    plen = plen_ref[...]                # (bB, P)
    y = y_ref[...]                      # (bB, bT)
    s_ = starts[:, None, :]             # (bB, 1, P)
    y_ = y[:, :, None]                  # (bB, bT, 1)
    tol = 1e-6 * jnp.maximum(1.0, jnp.abs(y_))
    cand = first_crossing_candidates(s_, c0[:, None, :], c1[:, None, :],
                                     c2[:, None, :], plen[:, None, :], y_, tol)
    cand = jnp.where(s_ < _PAD_HALF, cand, _BIG)
    out_ref[...] = jnp.min(cand, axis=-1)


def ppoly_first_crossing_pallas(starts: jnp.ndarray, coeffs: jnp.ndarray,
                                y: jnp.ndarray, *, block_b: int = 8,
                                block_t: int = 128, interpret: bool = True):
    """``pallas_call`` wrapper for batched first-crossing queries.

    starts (B, P) · coeffs (B, P, K<=3) · y (B, T) → (B, T) crossing times.
    """
    B, P = starts.shape
    T = y.shape[-1]
    assert coeffs.shape[-1] <= 3, "first crossing requires degree <= 2 input"
    assert B % block_b == 0 and T % block_t == 0, "pad inputs to block multiples"
    c0 = coeffs[..., 0]
    c1 = coeffs[..., 1] if coeffs.shape[-1] > 1 else jnp.zeros_like(c0)
    c2 = coeffs[..., 2] if coeffs.shape[-1] > 2 else jnp.zeros_like(c0)
    plen = jnp.concatenate([starts[:, 1:],
                            jnp.full((B, 1), PAD_START, starts.dtype)],
                           axis=1) - starts
    grid = (B // block_b, T // block_t)
    fn_spec = pl.BlockSpec((block_b, P), lambda i, j: (i, 0))
    y_spec = pl.BlockSpec((block_b, block_t), lambda i, j: (i, j))
    with jax.enable_x64(False):
        return pl.pallas_call(
            _first_crossing_kernel,
            grid=grid,
            in_specs=[fn_spec] * 5 + [y_spec],
            out_specs=y_spec,
            out_shape=jax.ShapeDtypeStruct((B, T), jnp.float32),
            interpret=interpret,
        )(starts, c0, c1, c2, plen, y)


def ppoly_eval_pallas(starts: jnp.ndarray, coeffs: jnp.ndarray, q: jnp.ndarray,
                      *, block_b: int = 8, block_t: int = 128,
                      interpret: bool = True) -> jnp.ndarray:
    """``pallas_call`` wrapper; shapes must be pre-padded to block multiples.

    starts (B, P) · coeffs (B, P, K) · q (B, T) → (B, T), all float32.
    """
    B, P = starts.shape
    K = coeffs.shape[-1]
    T = q.shape[-1]
    assert B % block_b == 0 and T % block_t == 0, "pad inputs to block multiples"

    grid = (B // block_b, T // block_t)
    kernel = functools.partial(_ppoly_kernel, n_pieces=P)
    fn_spec = pl.BlockSpec((block_b, P), lambda i, j: (i, 0))
    q_spec = pl.BlockSpec((block_b, block_t), lambda i, j: (i, j))
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[fn_spec, q_spec] + [fn_spec] * K,
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((B, T), jnp.float32),
            interpret=interpret,
        )(starts, q, *(coeffs[..., k] for k in range(K)))
