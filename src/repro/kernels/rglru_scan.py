"""Pallas TPU RG-LRU scan kernel.

Grid = (B, n_width_blocks, n_seq_blocks); the seq dim is sequential, carrying
the recurrent state h in VMEM scratch across seq blocks (TPU grid iteration
order makes the last dim innermost).  Within a block the recurrence runs as a
fori_loop over aligned 16-row tiles of the (blk_s, blk_w) VMEM block, rows
unrolled inside a tile — VPU elementwise work with the state vector resident
in registers/VMEM, which is how a TPU wants a width-parallel linear scan
(contrast a GPU chunked-scan with shared-memory staging).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(x_ref, a_ref, h0_ref, y_ref, hlast_ref, h_scr, *,
                  blk_s: int, rows: int, n_seq_blocks: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)  # (1, blk_w)

    def body(t, h):
        # a tile of `rows` rows per step, read from the refs at an aligned
        # offset (Mosaic indexes refs, not values, dynamically); the
        # recurrence runs over the tile's rows unrolled
        r0 = pl.multiple_of(t * rows, rows)
        a = jnp.exp(a_ref[0, pl.ds(r0, rows), :].astype(jnp.float32))
        g = (jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
             * x_ref[0, pl.ds(r0, rows), :].astype(jnp.float32))
        hs = []
        for r in range(rows):
            h = a[r:r + 1] * h + g[r:r + 1]
            hs.append(h)
        y_ref[0, pl.ds(r0, rows), :] = jnp.concatenate(hs).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, blk_s // rows, body, h_scr[...])
    h_scr[...] = h

    @pl.when(si == n_seq_blocks - 1)
    def _emit_final():
        hlast_ref[0] = h.astype(hlast_ref.dtype)


def rglru_scan_pallas(
    x: jnp.ndarray,      # (B, S, W)
    a_log: jnp.ndarray,  # (B, S, W)
    *,
    h0: Optional[jnp.ndarray] = None,
    blk_s: int = 256,
    blk_w: int = 512,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, S, W = x.shape
    blk_s = min(blk_s, S)
    blk_w = min(blk_w, W)
    assert S % blk_s == 0 and W % blk_w == 0, (S, W, blk_s, blk_w)
    ns, nw = S // blk_s, W // blk_w
    h0_in = (h0 if h0 is not None else jnp.zeros((B, W), x.dtype))
    # states travel as (B, 1, W): a (1, blk_w) block of a (B, W) array breaks
    # the TPU's (sublane, lane) tiling rule whenever B > 1
    h0_in = h0_in.reshape(B, 1, W)

    # 16 rows fill one bf16 (16, 128) tile (two f32 ones)
    rows = math.gcd(blk_s, 16)
    kernel = functools.partial(_rglru_kernel, blk_s=blk_s, rows=rows,
                               n_seq_blocks=ns)
    y, hlast = pl.pallas_call(
        kernel,
        grid=(B, nw, ns),
        in_specs=[
            pl.BlockSpec((1, blk_s, blk_w), lambda b, wi, si: (b, si, wi)),
            pl.BlockSpec((1, blk_s, blk_w), lambda b, wi, si: (b, si, wi)),
            pl.BlockSpec((1, 1, blk_w), lambda b, wi, si: (b, 0, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_s, blk_w), lambda b, wi, si: (b, si, wi)),
            pl.BlockSpec((1, 1, blk_w), lambda b, wi, si: (b, 0, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), x.dtype),
            jax.ShapeDtypeStruct((B, 1, W), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, blk_w), jnp.float32)],
        interpret=interpret,
    )(x, a_log, h0_in)
    return y, hlast.reshape(B, W)
