"""Pallas TPU Mamba-2 SSD kernel (chunked state-space duality).

Grid = (B, H, n_chunks) with the chunk dim sequential; each head's
inter-chunk state h (P, N) persists in VMEM scratch.  Each grid step does one
head's intra-chunk quadratic duality on the MXU (Q×Q score and decay
matrices) plus the state update — every operand is a 2-D tile, which is the
form Mosaic lowers.  The inputs are laid out head-major outside the kernel,
and the per-step decays come in as both a column and a row so that no
in-kernel transpose is needed.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dtc_ref, dtr_ref, b_ref, c_ref, y_ref, hout_ref,
                h_scr, *, n_chunks: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    A = a_ref[hi]                                   # this head's decay rate
    x = x_ref[0, 0].astype(jnp.float32)             # (Q, P)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)      # (Q, 1)
    dt_row = dtr_ref[0, 0].astype(jnp.float32)      # (1, Q)
    Bm = b_ref[0].astype(jnp.float32)               # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)               # (Q, N)
    Q, P = x.shape

    # Mosaic has no cumsum: the inclusive prefix sum over the chunk is a
    # matmul with a triangular ones matrix, in full f32 precision
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower = row >= col
    def exact(a, b):
        return jax.lax.dot_general(a, b,
                                   (((1,), (0,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)

    dA_col = dt_col * A
    cum_col = exact(jnp.where(lower, 1.0, 0.0), dA_col)          # (Q, 1)
    cum_row = exact(dt_row * A, jnp.where(row <= col, 1.0, 0.0))  # (1, Q)
    # the chunk's total decay, as a column of the height each use needs
    # (Mosaic broadcasts along sublanes or lanes, not both at once)
    end_q = exact(jnp.ones((Q, Q), jnp.float32), dA_col)         # (Q, 1)
    end_p = exact(jnp.ones((P, Q), jnp.float32), dA_col)         # (P, 1)
    xdt = x * dt_col                                             # (Q, P)

    # intra-chunk: y[q] = sum_{k<=q} exp(cum[q]-cum[k]) * (C_q·B_k) xdt[k]
    L = jnp.where(lower, jnp.exp(cum_col - cum_row), 0.0)       # (Q, Q)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_intra = jax.lax.dot_general(L * scores, xdt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    h = h_scr[...]                                               # (P, N)
    y_inter = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y_intra + y_inter * jnp.exp(cum_col)).astype(y_ref.dtype)

    # state update: h' = exp(cum[-1]) h + sum_k exp(cum[-1]-cum[k]) xdt[k] B_k
    xdt_w = xdt * jnp.exp(end_q - cum_col)                       # (Q, P)
    s_chunk = jax.lax.dot_general(xdt_w, Bm, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    h_scr[...] = jnp.exp(end_p) * h + s_chunk

    @pl.when(ci == n_chunks - 1)
    def _emit():
        hout_ref[0, 0] = h_scr[...]


def ssd_scan_pallas(
    x: jnp.ndarray,     # (B, S, H, P)
    dt: jnp.ndarray,    # (B, S, H)
    A: jnp.ndarray,     # (H,)
    Bmat: jnp.ndarray,  # (B, S, N)
    Cmat: jnp.ndarray,  # (B, S, N)
    *,
    chunk: int = 128,
    h0: Optional[jnp.ndarray] = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if h0 is not None:
        raise NotImplementedError("pallas ssd kernel starts from h=0; fold "
                                  "carried state via ops.ssd_decode_step")
    B, S, H, P = x.shape
    N = Bmat.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    xh = x.transpose(0, 2, 1, 3)                     # (B, H, S, P)
    dth = dt.transpose(0, 2, 1)                      # (B, H, S)
    kernel = functools.partial(_ssd_kernel, n_chunks=nc)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(A.astype(jnp.float32), xh, dth[..., None], dth[:, :, None, :],
      Bmat, Cmat)
    return y.transpose(0, 2, 1, 3), h_final
