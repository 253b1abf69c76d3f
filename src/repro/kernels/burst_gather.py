"""Pallas TPU burst gather: descriptor-driven packet-arena → contiguous batch.

This is the paper's DMA path as a TPU kernel: the NIC (loadgen) leaves
variable-length packets scattered across a pinned arena; consumers want a
dense (burst, width) tensor.  The descriptor ring (slot indices) is passed as
a **scalar-prefetch** operand — Pallas reads the indices in SMEM *before*
issuing each block's HBM→VMEM DMA, which is exactly the descriptor-cache →
descriptor-driven-DMA structure of a NIC RX queue (§3.1.4), and the burst is
the DCA staging unit (§5.2); one grid step stages one packet.

Non-TPU note (DESIGN.md §2): the gem5 changes themselves are register-level
x86 shims with no TPU analogue; this kernel is the *functional* equivalent —
userspace-owned descriptor-driven data movement with explicit staging.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(slots_ref, len_ref, arena_ref, out_ref, *, out_words: int):
    i = pl.program_id(0)
    # arena_ref block was DMA'd using the prefetched descriptor (see index_map)
    row = arena_ref[0, :, :out_words]                        # (1, out_words)
    # little-endian words: byte 4k+j of the frame is bits 8j..8j+7 of word k
    word = jax.lax.broadcasted_iota(jnp.int32, (1, out_words), 1)
    keep = jnp.clip(len_ref[i] - 4 * word, 0, 4)             # bytes kept
    low = (jnp.left_shift(jnp.uint32(1), (8 * keep).astype(jnp.uint32))
           - jnp.uint32(1))
    mask = jnp.where(keep == 4, jnp.uint32(0xFFFFFFFF), low)
    out_ref[0] = row & mask


def burst_gather_pallas(
    arena: jnp.ndarray,    # (n_slots, slot_size) uint8
    slots: jnp.ndarray,    # (n,) int32 descriptor slot indices
    lengths: jnp.ndarray,  # (n,) int32
    out_width: int,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    n = slots.shape[0]
    n_slots, slot_size = arena.shape
    if slot_size % 4:
        raise ValueError(f"slot_size {slot_size} is not a whole number of "
                         "32-bit words")
    w = min(out_width, slot_size)
    slot_words, out_words = slot_size // 4, -(-w // 4)
    # the TPU's vector unit works on 32-bit words, so the kernel moves
    # frames as uint32; a unit middle axis makes each block's last two dims
    # equal the array's, which the (sublane, lane) tiling rule accepts
    words = jax.lax.bitcast_convert_type(
        arena.reshape(n_slots, 1, slot_words, 4), jnp.uint32)

    def arena_map(i, slots_s, lens_s):
        # descriptor-driven DMA: the block row comes from the prefetched ring
        return (slots_s[i], 0, 0)

    def out_map(i, slots_s, lens_s):
        return (i, 0, 0)

    kernel = functools.partial(_gather_kernel, out_words=out_words)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, 1, slot_words), arena_map)],
        out_specs=pl.BlockSpec((1, 1, out_words), out_map),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, out_words), jnp.uint32),
        interpret=interpret,
    )(slots.astype(jnp.int32), lengths.astype(jnp.int32), words)
    out = jax.lax.bitcast_convert_type(out, jnp.uint8).reshape(n, 4 * out_words)
    if 4 * out_words != out_width:
        out = (out[:, :out_width] if 4 * out_words > out_width
               else jnp.pad(out, ((0, 0), (0, out_width - 4 * out_words))))
    return out
