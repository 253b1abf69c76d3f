"""Public jit'd kernel wrappers.

Every op has up to three interchangeable implementations:

* ``ref``      — naive oracle (ref.py), small shapes, ground truth;
* ``chunked``  — production pure-jnp path: memory-bounded, scan-based; this is
                 what the CPU dry-run lowers (and what XLA:TPU would run if
                 Pallas were disabled);
* ``pallas``   — the TPU kernel (explicit BlockSpec VMEM tiling); validated in
                 interpret mode against ``ref`` in tests.

Dispatch: ``impl="auto"`` picks pallas on TPU backends, chunked elsewhere.
``REPRO_FORCE_IMPL`` overrides that choice, and ``REPRO_PALLAS_INTERPRET=1``
runs every Pallas kernel in interpret mode (a CPU rehearsal of the TPU path).

Training through ``impl="pallas"`` attention differentiates a
``custom_vjp``: the Pallas kernel computes the forward, and the backward is
the VJP of the XLA path (:func:`xla_attention_impl` names which one), which
recomputes the scores chunk by chunk instead of saving them.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.axes import current_mesh, current_rules

from . import ref as _ref


def _auto_impl() -> str:
    if os.environ.get("REPRO_FORCE_IMPL"):
        return os.environ["REPRO_FORCE_IMPL"]
    return "pallas" if jax.default_backend() == "tpu" else "chunked"


def _interpret(interpret: bool) -> bool:
    return interpret or os.environ.get("REPRO_PALLAS_INTERPRET") == "1"


def _per_batch_shard(fn, *args, batched: Tuple[bool, ...]):
    """Call a Pallas kernel once per shard of the batch axes of the active
    mesh.  GSPMD cannot partition a Mosaic kernel, so under a mesh the call
    goes through ``shard_map``; every kernel here is independent across the
    batch, and every output leads with it.  ``batched`` marks which
    arguments lead with the batch axis (the others are replicated).  A batch
    the mesh cannot divide runs replicated."""
    mesh, rules = current_mesh(), current_rules()
    if mesh is None or rules is None:
        return fn(*args)
    axes = rules.resolve("batch")
    names = (axes,) if isinstance(axes, str) else tuple(axes or ())
    size = 1
    for a in names:
        size *= mesh.shape[a]
    n = next(x.shape[0] for x, b in zip(args, batched) if b)
    bspec = P(axes) if names and n % size == 0 else P()
    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(bspec if b else P() for b in batched),
        out_specs=bspec, check_vma=False)(*args)


# =============================================================================
# Flash attention (train/prefill)
# =============================================================================

def _chunked_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    causal: bool, window: int, q_offset: int, scale: float, q_chunk: int,
) -> jnp.ndarray:
    """Memory-bounded attention: scan over query chunks.

    Full/causal: each chunk attends to the whole KV with a mask (the causal
    flop-skip lives in the Pallas kernel / pair-scheduled variant).
    Sliding window: each chunk attends only to its (window + chunk) KV slice —
    exact O(S·W) flops, which is what makes 32k/500k SWA prefill lowerable.
    """
    B, Sq, H, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    qc = min(q_chunk, Sq)
    pad = (-Sq) % qc
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = q.shape[1] // qc
    qs = q.transpose(1, 0, 2, 3).reshape(nq, qc, B, H, Dh)
    # GQA via KV broadcast to H query heads: keeps the head axis evenly
    # sharded under TP (a (Hkv, group) reshape makes GSPMD re-lay-out the
    # uneven factor with all-to-alls)
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)

    use_window_slice = window > 0 and Skv > window + qc
    kv_span = min(Skv, window + qc) if use_window_slice else Skv

    # MXU-style numerics: bf16 inputs with f32 accumulation when the model
    # runs bf16 (halves attention dot traffic); full f32 for f32 inputs so
    # oracle comparisons stay exact
    dot_dt = q.dtype if q.dtype == jnp.bfloat16 else jnp.float32

    def body(_, inp):
        i, q_c = inp  # q_c: (qc, B, H, Dh)
        qpos = q_offset + i * qc + jnp.arange(qc)
        if use_window_slice:
            start = jnp.clip(q_offset + i * qc + qc - kv_span, 0, Skv - kv_span)
            k_c = jax.lax.dynamic_slice_in_dim(k, start, kv_span, axis=1)
            v_c = jax.lax.dynamic_slice_in_dim(v, start, kv_span, axis=1)
            kpos = start + jnp.arange(kv_span)
        else:
            k_c, v_c, kpos = k, v, jnp.arange(Skv)
        s = jnp.einsum("qbhd,bkhd->bhqk", q_c.astype(dot_dt),
                       k_c.astype(dot_dt),
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((qc, kv_span), dtype=bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->qbhd", p.astype(dot_dt),
                       v_c.astype(dot_dt),
                       preferred_element_type=jnp.float32)
        return None, o.astype(q.dtype)

    # flash-attention backward semantics: recompute scores per chunk instead
    # of saving softmax activations (O(S^2) f32) for the bwd pass
    body = jax.checkpoint(body)
    _, outs = jax.lax.scan(body, None, (jnp.arange(nq), qs))
    out = outs.reshape(nq * qc, B, H, Dh).transpose(1, 0, 2, 3)
    return out[:, :Sq]


def _paired_causal_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    scale: float, chunk: int,
) -> jnp.ndarray:
    """Exact-flops causal attention: only valid (q-block, kv-block) pairs.

    The plain chunked path computes the full S×S rectangle and masks half of
    it away — 2× wasted attention flops in the lowered HLO (EXPERIMENTS.md
    §Perf iter 6).  Here the scan runs over the static list of causal block
    pairs (i, j≤i), carrying flash-style online-softmax state per q-block;
    flops are S²/2·(1+1/n) exact.  Pads S to a chunk multiple; GQA KV is
    broadcast to query heads (even TP sharding).
    """
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qc = min(chunk, S)
    pad = (-S) % qc
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    Sp = q.shape[1]
    n = Sp // qc
    qT = q.transpose(1, 0, 2, 3)  # (S, B, H, Dh) — row-sliceable
    kT = k.transpose(1, 0, 2, 3)
    vT = v.transpose(1, 0, 2, 3)
    dot_dt = q.dtype if q.dtype == jnp.bfloat16 else jnp.float32

    # static causal pair schedule, grouped by q-block (j ascending within i)
    import numpy as _np
    pairs = [(i, j) for i in range(n) for j in range(i + 1)]
    i_arr = jnp.asarray(_np.array([p[0] for p in pairs], _np.int32))
    j_arr = jnp.asarray(_np.array([p[1] for p in pairs], _np.int32))

    def body(carry, ij):
        i, j = ij
        acc, m, l, out = carry
        fresh = j == 0  # first kv block of a new q block: reset the state
        acc = jnp.where(fresh, 0.0, acc)
        m = jnp.where(fresh, NEG_INF_PAIRED, m)
        l = jnp.where(fresh, 0.0, l)
        q_c = jax.lax.dynamic_slice_in_dim(qT, i * qc, qc, axis=0)
        k_c = jax.lax.dynamic_slice_in_dim(kT, j * qc, qc, axis=0)
        v_c = jax.lax.dynamic_slice_in_dim(vT, j * qc, qc, axis=0)
        s = jnp.einsum("qbhd,kbhd->bhqk", q_c.astype(dot_dt),
                       k_c.astype(dot_dt),
                       preferred_element_type=jnp.float32) * scale
        # mask matters only on the diagonal block (i == j)
        qpos = i * qc + jnp.arange(qc)
        kpos = j * qc + jnp.arange(qc)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, NEG_INF_PAIRED)
        m_new = jnp.maximum(m, s.max(axis=-1))           # (B, H, qc)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,kbhd->qbhd", p.astype(dot_dt),
                        v_c.astype(dot_dt),
                        preferred_element_type=jnp.float32)
        acc = acc * alpha.transpose(2, 0, 1)[..., None] + pv
        # publish the (so-far-normalized) rows; the last j for each i wins
        l_safe = jnp.where(l == 0.0, 1.0, l)
        norm = (acc / l_safe.transpose(2, 0, 1)[..., None]).astype(out.dtype)
        out = jax.lax.dynamic_update_slice_in_dim(out, norm, i * qc, axis=0)
        return (acc, m_new, l, out), None

    init = (
        jnp.zeros((qc, B, H, Dh), jnp.float32),
        jnp.full((B, H, qc), NEG_INF_PAIRED, jnp.float32),
        jnp.zeros((B, H, qc), jnp.float32),
        jnp.zeros((Sp, B, H, Dh), q.dtype),
    )
    body = jax.checkpoint(body)  # flash bwd semantics: recompute per pair
    (_, _, _, out), _ = jax.lax.scan(body, init, (i_arr, j_arr))
    return out.transpose(1, 0, 2, 3)[:, :S]


NEG_INF_PAIRED = -1e30


def xla_attention_impl(q_len: int, kv_len: int, *, causal: bool = True,
                       window: int = 0, q_offset: int = 0) -> str:
    """Which XLA attention path runs for these shapes: ``"paired"`` (the
    exact-flops causal pair schedule) or ``"chunked"``."""
    if (causal and window == 0 and q_offset == 0 and q_len == kv_len
            and q_len > 1 and os.environ.get("REPRO_NO_PAIRED") != "1"):
        return "paired"
    return "chunked"


def _xla_attention(q, k, v, *, causal: bool, window: int, q_offset: int,
                   scale: float, q_chunk: int) -> jnp.ndarray:
    if xla_attention_impl(q.shape[1], k.shape[1], causal=causal,
                          window=window, q_offset=q_offset) == "paired":
        return _paired_causal_attention(q, k, v, scale=scale, chunk=q_chunk)
    return _chunked_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset, scale=scale, q_chunk=q_chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _pallas_attention(q, k, v, causal, window, q_offset, scale, q_chunk,
                      interpret):
    from .flash_attention import flash_attention_pallas
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, softmax_scale=scale,
                                  interpret=interpret)


def _pallas_attention_fwd(q, k, v, causal, window, q_offset, scale, q_chunk,
                          interpret):
    out = _pallas_attention(q, k, v, causal, window, q_offset, scale,
                            q_chunk, interpret)
    return out, (q, k, v)


def _pallas_attention_bwd(causal, window, q_offset, scale, q_chunk,
                          interpret, res, g):
    _, vjp = jax.vjp(functools.partial(
        _xla_attention, causal=causal, window=window, q_offset=q_offset,
        scale=scale, q_chunk=q_chunk), *res)
    return vjp(g)


_pallas_attention.defvjp(_pallas_attention_fwd, _pallas_attention_bwd)


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, Dh)
    k: jnp.ndarray,  # (B, Skv, Hkv, Dh)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    softmax_scale: Optional[float] = None,
    q_chunk: int = 256,
    impl: str = "auto",
    interpret: bool = False,
) -> jnp.ndarray:
    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    impl = _auto_impl() if impl == "auto" else impl
    if impl == "ref":
        return _ref.mha(q, k, v, causal=causal, window=window, q_offset=q_offset,
                        softmax_scale=scale)
    if impl == "pallas":
        interpret = _interpret(interpret)
        return _per_batch_shard(
            lambda q, k, v: _pallas_attention(q, k, v, causal, window,
                                              q_offset, scale, q_chunk,
                                              interpret),
            q, k, v, batched=(True, True, True))
    return _xla_attention(q, k, v, causal=causal, window=window,
                          q_offset=q_offset, scale=scale, q_chunk=q_chunk)


# =============================================================================
# Decode attention (single new token against a KV cache)
# =============================================================================

def decode_attention(
    q: jnp.ndarray,          # (B, H, Dh)
    k_cache: jnp.ndarray,    # (B, S, Hkv, Dh)
    v_cache: jnp.ndarray,
    cache_len: jnp.ndarray,  # (B,) valid entries (ring caches: min(pos+1, W))
    *,
    softmax_scale: Optional[float] = None,
    impl: str = "auto",
    interpret: bool = False,
) -> jnp.ndarray:
    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    impl = _auto_impl() if impl == "auto" else impl
    if impl == "pallas":
        from .decode_attention import decode_attention_pallas
        return _per_batch_shard(
            functools.partial(decode_attention_pallas, softmax_scale=scale,
                              interpret=_interpret(interpret)),
            q, k_cache, v_cache, cache_len, batched=(True,) * 4)
    # chunked == ref math here (scores are (B,H,S): already memory-linear)
    return _ref.decode_attention(q, k_cache, v_cache, cache_len,
                                 softmax_scale=scale)


# =============================================================================
# RG-LRU scan (recurrentgemma)
# =============================================================================

def rglru_scan(
    x: jnp.ndarray,      # (B, S, W)
    a_log: jnp.ndarray,  # (B, S, W) log-decay (<= 0)
    *,
    h0: Optional[jnp.ndarray] = None,   # (B, W) initial state
    impl: str = "auto",
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (hidden states (B,S,W), final state (B,W))."""
    impl = _auto_impl() if impl == "auto" else impl
    if impl == "pallas":
        from .rglru_scan import rglru_scan_pallas
        fn = functools.partial(rglru_scan_pallas,
                               interpret=_interpret(interpret))
        if h0 is None:
            return _per_batch_shard(fn, x, a_log, batched=(True, True))
        return _per_batch_shard(lambda x, a, h: fn(x, a, h0=h), x, a_log, h0,
                                batched=(True, True, True))
    if impl == "ref":
        hs = _ref.rglru(x, a_log)
        if h0 is not None:
            raise NotImplementedError("ref path has no h0")
        return hs, hs[:, -1]
    # production jnp: log-depth associative scan over (a, b) pairs
    a = jnp.exp(a_log.astype(jnp.float32))
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * x.astype(jnp.float32)
    if h0 is not None:
        # fold the carried-in state into the first step
        b = b.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    aa, bb = jax.lax.associative_scan(combine, (a, b), axis=1)
    hs = bb.astype(x.dtype)
    return hs, hs[:, -1]


def rglru_decode_step(
    x_t: jnp.ndarray, a_log_t: jnp.ndarray, h: jnp.ndarray
) -> jnp.ndarray:
    """One-token RG-LRU update: (B, W) state in/out."""
    a = jnp.exp(a_log_t.astype(jnp.float32))
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * x_t.astype(jnp.float32)
    return (a * h.astype(jnp.float32) + b).astype(h.dtype)


# =============================================================================
# Mamba-2 SSD (chunked state-space duality)
# =============================================================================

def _segsum(x: jnp.ndarray) -> jnp.ndarray:
    """(..., Q) -> (..., Q, Q) lower-triangular pairwise cumulative sums."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]  # sum over (j, i]
    mask = jnp.tril(jnp.ones((Q, Q), dtype=bool), 0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_scan(
    x: jnp.ndarray,     # (B, S, H, P)
    dt: jnp.ndarray,    # (B, S, H) positive
    A: jnp.ndarray,     # (H,) negative
    Bmat: jnp.ndarray,  # (B, S, N)
    Cmat: jnp.ndarray,  # (B, S, N)
    *,
    chunk: int = 128,
    h0: Optional[jnp.ndarray] = None,  # (B, H, P, N)
    impl: str = "auto",
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD: intra-chunk quadratic attention-duality + inter-chunk
    recurrence. Returns (y (B,S,H,P), final state (B,H,P,N))."""
    impl = _auto_impl() if impl == "auto" else impl
    if impl == "pallas":
        from .ssd_scan import ssd_scan_pallas
        return _per_batch_shard(
            functools.partial(ssd_scan_pallas, chunk=chunk, h0=h0,
                              interpret=_interpret(interpret)),
            x, dt, A, Bmat, Cmat, batched=(True, True, False, True, True))
    if impl == "ref":
        y = _ref.ssd(x, dt, A, Bmat, Cmat)
        return y, jnp.zeros((x.shape[0], x.shape[2], x.shape[3], Bmat.shape[-1]),
                            jnp.float32)

    B_, S, H, P = x.shape
    N = Bmat.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        # zero-pad the tail: dt=0 rows leave the state untouched (decay=1,
        # update=0) and their outputs are sliced away below
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0)))
    S_pad = S + pad
    nc = S_pad // Q
    xf = x.astype(jnp.float32).reshape(B_, nc, Q, H, P)
    dtf = dt.astype(jnp.float32).reshape(B_, nc, Q, H)
    Bf = Bmat.astype(jnp.float32).reshape(B_, nc, Q, N)
    Cf = Cmat.astype(jnp.float32).reshape(B_, nc, Q, N)
    Af = A.astype(jnp.float32)

    # per-step log decay within chunks: (B, nc, Q, H)
    dA = dtf * Af[None, None, None, :]
    xdt = xf * dtf[..., None]  # dt-weighted inputs

    # bf16 dot inputs (f32 accumulate) when the model runs bf16 — the decay
    # accumulation (cumsum/exp) stays f32 for stability
    dot_dt = x.dtype if x.dtype == jnp.bfloat16 else jnp.float32

    # ---- intra-chunk (quadratic, attention-like duality) --------------------
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))  # (B, nc, H, Q, Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cf.astype(dot_dt),
                        Bf.astype(dot_dt),
                        preferred_element_type=jnp.float32)  # (B, nc, Q, Q)
    # scores (q,k) * per-head decay L (q,k), applied to dt-weighted input at k
    w_qk = (L * scores[:, :, None]).astype(dot_dt)  # (B, nc, H, Q, Q)
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", w_qk, xdt.astype(dot_dt),
                         preferred_element_type=jnp.float32)

    # ---- chunk summary states ----------------------------------------------
    dA_cum = jnp.cumsum(dA, axis=2)                      # (B, nc, Q, H)
    decay_to_end = jnp.exp(dA_cum[:, :, -1:, :] - dA_cum)  # (B, nc, Q, H)
    xdt_w = (xdt * decay_to_end[..., None]).astype(dot_dt)
    S_chunk = jnp.einsum("bcqn,bcqhp->bchpn", Bf.astype(dot_dt), xdt_w,
                         preferred_element_type=jnp.float32)

    # ---- inter-chunk recurrence (scan over nc chunks) ------------------------
    chunk_decay = jnp.exp(dA_cum[:, :, -1, :])  # (B, nc, H) total decay per chunk

    def step(h, inp):
        s_c, d_c = inp  # (B,H,P,N), (B,H)
        h_next = d_c[..., None, None] * h + s_c
        return h_next, h  # emit state *entering* the chunk

    s_sw = jnp.moveaxis(S_chunk, 1, 0)
    d_sw = jnp.moveaxis(chunk_decay, 1, 0)
    h_init = (h0.astype(jnp.float32) if h0 is not None
              else jnp.zeros((B_, H, P, N), jnp.float32))
    h_final, h_enter = jax.lax.scan(step, h_init, (s_sw, d_sw))
    h_enter = jnp.moveaxis(h_enter, 0, 1)  # (B, nc, H, P, N)

    # ---- inter-chunk contribution ------------------------------------------
    decay_from_start = jnp.exp(dA_cum)  # (B, nc, Q, H)
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cf, decay_from_start, h_enter)

    y = (y_intra + y_inter).reshape(B_, S_pad, H, P)[:, :S].astype(x.dtype)
    return y, h_final


def ssd_decode_step(
    x_t: jnp.ndarray,   # (B, H, P)
    dt_t: jnp.ndarray,  # (B, H)
    A: jnp.ndarray,     # (H,)
    B_t: jnp.ndarray,   # (B, N)
    C_t: jnp.ndarray,   # (B, N)
    h: jnp.ndarray,     # (B, H, P, N) f32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One-token SSD update. Returns (y (B,H,P), new state)."""
    decay = jnp.exp(A.astype(jnp.float32)[None] * dt_t.astype(jnp.float32))
    update = (dt_t[..., None, None] * x_t.astype(jnp.float32)[..., None]
              ) * B_t.astype(jnp.float32)[:, None, None, :]
    h_new = decay[..., None, None] * h + update
    y = jnp.einsum("bhpn,bn->bhp", h_new, C_t.astype(jnp.float32))
    return y.astype(x_t.dtype), h_new


# =============================================================================
# Burst gather (packet arena -> contiguous batch; the DMA/DCA device path)
# =============================================================================

def burst_gather(
    arena: jnp.ndarray,    # (n_slots, slot_size) uint8
    slots: jnp.ndarray,    # (n,) int32
    lengths: jnp.ndarray,  # (n,) int32
    out_width: int,
    *,
    impl: str = "auto",
    interpret: bool = False,
) -> jnp.ndarray:
    impl = _auto_impl() if impl == "auto" else impl
    if impl == "pallas":
        from .burst_gather import burst_gather_pallas
        return burst_gather_pallas(arena, slots, lengths, out_width,
                                   interpret=_interpret(interpret))
    return _ref.burst_gather(arena, slots, lengths, out_width)
