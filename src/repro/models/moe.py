"""Token-choice top-k Mixture-of-Experts with a unified EP×FP sharding scheme.

Covers mixtral-8x7b (8 experts, top-2, every layer) and llama4-maverick
(128 experts, top-1, every other layer, + shared expert).

Sharding design (DESIGN.md §4): the `model` mesh axis (size TP) is factored
into ``ep × fp`` where ``ep = gcd(E, TP)`` shards the expert dim and ``fp``
shards each expert's FFN hidden dim.  Expert weights are stored pre-blocked as
``(TP, E/ep, D, F/fp)`` so a single ``P('model', ...)`` in_spec hands every
shard exactly its expert/F-slice block:

* llama4 (E=128, TP=16): ep=16, fp=1  → true expert parallelism, 8 experts/shard
* mixtral (E=8,  TP=16): ep=8,  fp=2  → EP over 8 × tensor-split FFN over 2

Inside shard_map, tokens are replicated over `model`; each shard gathers the
tokens routed to its local experts into a fixed-capacity buffer (capacity
dropping, Switch-style), runs the expert FFN on its F-slice, scatters partial
outputs back, and one psum over `model` combines everything (this psum is the
layer's EP collective).  The D dim of expert weights is additionally sharded
over `data` (FSDP); the explicit all_gather over `data` inside the shard_map
is the FSDP parameter gather.

Without a mesh (smoke tests) the same math runs unsharded via `_moe_compute`.
"""
from __future__ import annotations

import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.axes import current_mesh, current_rules
from .config import ModelConfig
from .layers import Params, _normal, cdt, dt, init_mlp, apply_mlp


def _ep_fp(cfg: ModelConfig, tp: int) -> Tuple[int, int]:
    ep = math.gcd(cfg.n_experts, tp)
    fp = tp // ep
    return ep, fp


def init_moe_layer(cfg: ModelConfig, key, tp_hint: int = 16) -> Params:
    """Expert weights stored in the (TP, E/ep, D, F/fp) blocked layout.

    ``tp_hint`` fixes the blocking at init; running on a mesh with a different
    model-axis size requires re-blocking (checkpoint manager handles that).
    """
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ep, fp = _ep_fp(cfg, tp_hint)
    e_loc, f_loc = E // ep, F // fp
    k_r, k_g, k_u, k_d, k_s = jax.random.split(key, 5)
    out_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    p = {
        "router": _normal(k_r, (D, E), 0.02, jnp.float32),
        "w_gate": _normal(k_g, (tp_hint, e_loc, D, f_loc), 0.02, dt(cfg)),
        "w_up": _normal(k_u, (tp_hint, e_loc, D, f_loc), 0.02, dt(cfg)),
        "w_down": _normal(k_d, (tp_hint, e_loc, f_loc, D), out_scale, dt(cfg)),
    }
    if cfg.n_shared_experts > 0:
        p["shared"] = init_mlp(cfg, k_s, d_ff=cfg.n_shared_experts * cfg.d_ff)
    return p


def _route(cfg: ModelConfig, router: jnp.ndarray, x2d: jnp.ndarray
           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (expert_idx (T,k), combine_weights (T,k) f32, aux_loss scalar)."""
    logits = (x2d.astype(jnp.float32) @ router).astype(jnp.float32)  # (T, E)
    k = cfg.experts_per_token
    vals, idx = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(vals, axis=-1)
    # Switch-style load-balancing aux + router z-loss
    probs = jax.nn.softmax(logits, axis=-1)
    me = probs.mean(axis=0)                                  # (E,)
    ce = jnp.zeros((cfg.n_experts,), jnp.float32)
    ce = ce.at[idx.reshape(-1)].add(1.0) / (x2d.shape[0] * k)
    aux = cfg.n_experts * jnp.sum(me * ce) * cfg.router_aux_coef
    zloss = 1e-4 * jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return idx, weights, aux + zloss


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(math.ceil(n_tokens * cfg.experts_per_token * cfg.capacity_factor
                      / cfg.n_experts))
    return max(4, c)


def _dispatch_indices(
    cfg: ModelConfig, idx: jnp.ndarray, e_lo: jnp.ndarray, e_hi: jnp.ndarray,
    n_local: int, capacity: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compute buffer positions for assignments routed to local experts.

    idx: (T, k) global expert ids.  Local experts are [e_lo, e_hi).
    Returns (flat buffer position (T*k,) int32 with -1 for non-local/overflow,
             local expert id per assignment (T*k,)).
    """
    T, k = idx.shape
    flat = idx.reshape(-1)
    local = (flat >= e_lo) & (flat < e_hi)
    loc_e = jnp.where(local, flat - e_lo, n_local)  # overflow bucket n_local
    onehot = jax.nn.one_hot(loc_e, n_local + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    my_pos = jnp.take_along_axis(pos, loc_e[:, None], axis=1)[:, 0]
    ok = local & (my_pos < capacity)
    buf_pos = jnp.where(ok, loc_e * capacity + my_pos, -1)
    return buf_pos, loc_e


def _expert_ffn(cfg: ModelConfig, wg, wu, wd, buf: jnp.ndarray) -> jnp.ndarray:
    """buf: (E_loc, C, D) -> (E_loc, C, D) through each expert's (sliced) FFN."""
    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(cdt(cfg)))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(cdt(cfg)))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(cdt(cfg)) * u
    return jnp.einsum("ecf,efd->ecd", h, wd.astype(cdt(cfg)))


def _moe_shard_body(cfg: ModelConfig, capacity: int, e_loc: int, fp: int,
                    axis_names: Tuple[str, ...], gather_weights: bool,
                    tokens_data_sharded: bool = True):
    """Returns the per-shard function for shard_map.

    Two data-movement modes, auto-selected by apply_moe (napkin math over
    weight-gather vs. activation-psum bytes):

    * ``gather_weights=True`` (token-heavy, e.g. training): FSDP all-gather
      the expert weights over `data` once per layer and compute locally —
      the gather amortizes over tens of thousands of tokens.
    * ``gather_weights=False`` (token-light, e.g. decode): weights never move.
      The (tiny) token batch is first all-gathered over `data` so every shard
      holds the SAME tokens, each shard computes up/gate partials with its
      D-slice of the weights, the partials are psum'd over `data`, the down
      projection emits this shard's D-slice which is all-gathered back, and
      each shard finally slices out its own batch rows.  For 400B-scale
      decode this moves ~MBs of activations instead of ~GBs of experts.
    """

    def body(x_loc, router, wg, wu, wd):
        # x_loc: (B_loc, S, D) — sharded over data/pod (batch), replicated
        # over model.  wg/wu: (1, e_loc, D/dp, f_loc); wd: (1, e_loc, f_loc,
        # D/dp) — this shard's expert block, D sharded over `data` (FSDP).
        B_loc, S, D = x_loc.shape
        x2d = x_loc.reshape(-1, D)
        T = x2d.shape[0]
        if not gather_weights and tokens_data_sharded:
            # weight-stationary mode: all shards must see the same tokens
            x2d = jax.lax.all_gather(x2d, "data", axis=0, tiled=True)
        T_eff = x2d.shape[0]
        idx, weights, aux = _route(cfg, router, x2d)
        shard_id = jax.lax.axis_index("model")
        ep_group = shard_id // fp
        e_lo = ep_group * e_loc
        cap = capacity if gather_weights else capacity * (T_eff // max(T, 1))
        buf_pos, _ = _dispatch_indices(cfg, idx, e_lo, e_lo + e_loc, e_loc,
                                       cap)
        k = cfg.experts_per_token
        # gather tokens into the capacity buffer (dropped/-1 -> scratch row)
        safe_pos = jnp.where(buf_pos >= 0, buf_pos, e_loc * cap)
        buf = jnp.zeros((e_loc * cap + 1, D), cdt(cfg))
        src = jnp.repeat(x2d, k, axis=0).astype(cdt(cfg))
        buf = buf.at[safe_pos].set(src)
        buf = buf[:-1].reshape(e_loc, cap, D)

        if gather_weights:
            wg_f = jax.lax.all_gather(wg[0], "data", axis=1, tiled=True)
            wu_f = jax.lax.all_gather(wu[0], "data", axis=1, tiled=True)
            wd_f = jax.lax.all_gather(wd[0], "data", axis=2, tiled=True)
            out_buf = _expert_ffn(cfg, wg_f, wu_f, wd_f, buf).reshape(-1, D)
        else:
            # weight-stationary: contract this shard's D-slice, psum partials
            n_dp = jax.lax.axis_size("data")
            d_loc = D // n_dp
            d_lo = jax.lax.axis_index("data") * d_loc
            buf_d = jax.lax.dynamic_slice_in_dim(buf, d_lo, d_loc, axis=2)
            g = jnp.einsum("ecd,edf->ecf", buf_d, wg[0].astype(cdt(cfg)))
            u = jnp.einsum("ecd,edf->ecf", buf_d, wu[0].astype(cdt(cfg)))
            gu = jax.lax.psum(
                jnp.stack([g, u]).astype(jnp.float32), "data")  # partial→full
            h = (jax.nn.silu(gu[0]) * gu[1]).astype(cdt(cfg))
            out_d = jnp.einsum("ecf,efd->ecd", h, wd[0].astype(cdt(cfg)))
            out_buf = jax.lax.all_gather(
                out_d, "data", axis=2, tiled=True).reshape(-1, D)

        out_buf = jnp.concatenate([out_buf, jnp.zeros((1, D), out_buf.dtype)])
        # combine: weighted scatter back to token order
        gathered = out_buf[jnp.where(buf_pos >= 0, buf_pos, e_loc * cap)]
        w_flat = weights.reshape(-1, 1).astype(jnp.float32)
        w_flat = jnp.where((buf_pos >= 0)[:, None], w_flat, 0.0)
        contrib = (gathered.astype(jnp.float32) * w_flat).reshape(T_eff, k, D)
        y = contrib.sum(axis=1)
        # bf16 on the wire: the psum over `model` carries the combined expert
        # outputs; f32 buys nothing after the f32 combine-weight multiply
        y = jax.lax.psum(y.astype(cdt(cfg)), "model")
        if not gather_weights and tokens_data_sharded:
            # slice back this data shard's own rows
            y = jax.lax.dynamic_slice_in_dim(
                y, jax.lax.axis_index("data") * T, T, axis=0)
        # aux varies over data shards (different tokens) → make it a true
        # global mean so the out_spec P() (replicated) is sound
        aux = jax.lax.pmean(aux, axis_name=axis_names)
        return y.reshape(B_loc, S, D).astype(x_loc.dtype), aux

    return body


def _moe_compute_local(cfg: ModelConfig, p: Params, x: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-device path: all experts local, same capacity semantics."""
    B, S, D = x.shape
    x2d = x.reshape(-1, D)
    T = x2d.shape[0]
    idx, weights, aux = _route(cfg, p["router"], x2d)
    capacity = _capacity(cfg, T)
    E = cfg.n_experts
    # reassemble full expert weights from the blocked layout
    tp = p["w_gate"].shape[0]
    ep, fp = _ep_fp(cfg, tp)
    e_loc, f_loc = E // ep, cfg.d_ff // fp

    wg = jnp.concatenate(
        [p["w_gate"].reshape(ep, fp, e_loc, D, f_loc)[:, i] for i in range(fp)],
        axis=-1).reshape(E, D, cfg.d_ff)
    wu = jnp.concatenate(
        [p["w_up"].reshape(ep, fp, e_loc, D, f_loc)[:, i] for i in range(fp)],
        axis=-1).reshape(E, D, cfg.d_ff)
    wd = jnp.concatenate(
        [p["w_down"].reshape(ep, fp, e_loc, f_loc, D)[:, i] for i in range(fp)],
        axis=-2).reshape(E, cfg.d_ff, D)

    buf_pos, _ = _dispatch_indices(cfg, idx, jnp.int32(0), jnp.int32(E), E,
                                   capacity)
    k = cfg.experts_per_token
    safe_pos = jnp.where(buf_pos >= 0, buf_pos, E * capacity)
    buf = jnp.zeros((E * capacity + 1, D), cdt(cfg))
    buf = buf.at[safe_pos].set(jnp.repeat(x2d, k, axis=0).astype(cdt(cfg)))
    buf = buf[:-1].reshape(E, capacity, D)
    out_buf = _expert_ffn(cfg, wg, wu, wd, buf).reshape(-1, D)
    out_buf = jnp.concatenate([out_buf, jnp.zeros((1, D), out_buf.dtype)])
    gathered = out_buf[safe_pos]
    w_flat = weights.reshape(-1, 1).astype(jnp.float32)
    w_flat = jnp.where((buf_pos >= 0)[:, None], w_flat, 0.0)
    y = (gathered.astype(jnp.float32) * w_flat).reshape(T, k, D).sum(axis=1)
    return y.reshape(B, S, D).astype(x.dtype), aux


def apply_moe(cfg: ModelConfig, p: Params, x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN. Returns (y, aux_loss). Adds shared expert if configured."""
    mesh = current_mesh()
    rules = current_rules()
    use_shard_map = (
        mesh is not None and rules is not None
        and "model" in mesh.axis_names and "data" in mesh.axis_names
        and p["w_gate"].shape[0] == mesh.shape["model"]
    )
    if use_shard_map:
        tp = mesh.shape["model"]
        ep, fp = _ep_fp(cfg, tp)
        e_loc = cfg.n_experts // ep
        B, S, D = x.shape
        batch_axes = rules.resolve("batch")
        if isinstance(batch_axes, str):
            batch_axes = (batch_axes,)
        n_batch_shards = 1
        for a in (batch_axes or ()):
            if a is not None:
                n_batch_shards *= mesh.shape[a]
        if batch_axes is None or B % n_batch_shards != 0:
            # tiny/odd batches (e.g. long-context decode, B=1): replicate
            # tokens over the DP axes; EP still splits the expert work
            batch_axes = None
            n_batch_shards = 1
        t_loc = (B // n_batch_shards) * S
        capacity = _capacity(cfg, t_loc)
        # napkin math: weight-gather bytes vs weight-stationary bytes per
        # layer.  Stationary mode pays: the token all-gather over data (every
        # shard needs the same tokens), the g+u partial psum (f32, ring 2x),
        # and n_dp-fold compute replication is tolerated only when the token
        # count is tiny — all captured by scaling with T_eff = t_loc * n_dp.
        n_dp = mesh.shape["data"]
        f_loc = cfg.d_ff // fp
        gather_bytes = 3 * e_loc * D * f_loc * 2            # 3 weight mats bf16
        cap_eff = capacity * n_dp
        act_bytes = (2 * e_loc * cap_eff * f_loc * 4 * 2    # g+u psum, f32 ring
                     + 2 * t_loc * n_dp * D * 2)            # token gather + out
        gather_weights = gather_bytes * (n_dp - 1) / n_dp < act_bytes
        force = os.environ.get("REPRO_MOE_FORCE_GATHER")
        if force is not None and force != "":
            gather_weights = force == "1"
        tokens_data_sharded = False
        for a in (batch_axes or ()):
            if a == "data":
                tokens_data_sharded = True
        body = _moe_shard_body(cfg, capacity, e_loc, fp,
                               tuple(mesh.axis_names), gather_weights,
                               tokens_data_sharded)
        xspec = P(batch_axes, None, None)
        wspec = P("model", None, "data", None)
        wdspec = P("model", None, None, "data")
        y, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(xspec, P(), wspec, wspec, wdspec),
            out_specs=(xspec, P()),
            check_vma=False,
        )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    else:
        y, aux = _moe_compute_local(cfg, p, x)
    if "shared" in p:
        y = y + apply_mlp(cfg, p["shared"], x)
    return y, aux
