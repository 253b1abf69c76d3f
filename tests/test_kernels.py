"""Per-kernel validation: Pallas (interpret=True) and chunked-jnp vs ref.py
oracles, swept over shapes and dtypes."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.burst_gather import burst_gather_pallas
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rglru_scan import rglru_scan_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

RNG = np.random.default_rng(0)


def _qkv(B, S, H, Hkv, Dh, dtype):
    q = jnp.asarray(RNG.normal(size=(B, S, H, Dh)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, Dh)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, Dh)), dtype)
    return q, k, v


ATTN_SWEEP = [
    # B, S, H, Hkv, Dh, causal, window
    (1, 128, 2, 1, 64, True, 0),
    (2, 256, 4, 2, 32, True, 0),
    (1, 256, 2, 2, 64, False, 0),     # bidirectional (encoder)
    (1, 384, 2, 1, 32, True, 128),    # sliding window
    (2, 128, 8, 2, 16, True, 0),      # deep GQA group
]


@pytest.mark.parametrize("B,S,H,Hkv,Dh,causal,window", ATTN_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_pallas_vs_ref(B, S, H, Hkv, Dh, causal, window, dtype):
    q, k, v = _qkv(B, S, H, Hkv, Dh, dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 blk_q=128, blk_k=128, interpret=True)
    want = ref.mha(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.array(got, np.float32),
                               np.array(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,S,H,Hkv,Dh,causal,window", ATTN_SWEEP)
def test_chunked_attention_vs_ref(B, S, H, Hkv, Dh, causal, window):
    q, k, v = _qkv(B, S, H, Hkv, Dh, jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="chunked", q_chunk=64)
    want = ref.mha(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_chunked_attention_ragged_seq():
    """Non-chunk-multiple sequence lengths must pad/unpad correctly."""
    q, k, v = _qkv(1, 100, 2, 1, 16, jnp.float32)
    got = ops.flash_attention(q, k, v, impl="chunked", q_chunk=32)
    want = ref.mha(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,H,Hkv,Dh", [(2, 512, 4, 2, 64), (1, 300, 2, 1, 32),
                                          (3, 128, 6, 3, 16)])
def test_decode_attention_pallas_vs_ref(B, S, H, Hkv, Dh):
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    kc = jnp.asarray(RNG.normal(size=(B, S, Hkv, Dh)), jnp.float32)
    vc = jnp.asarray(RNG.normal(size=(B, S, Hkv, Dh)), jnp.float32)
    cl = jnp.asarray(RNG.integers(1, S + 1, size=(B,)), jnp.int32)
    got = decode_attention_pallas(q, kc, vc, cl, blk_k=128, interpret=True)
    want = ref.decode_attention(q, kc, vc, cl)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,W,blk_s,blk_w", [(2, 256, 512, 64, 128),
                                               (1, 128, 256, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_pallas_vs_ref(B, S, W, blk_s, blk_w, dtype):
    x = jnp.asarray(RNG.normal(size=(B, S, W)), dtype)
    al = jnp.asarray(-np.abs(RNG.normal(size=(B, S, W))) * 0.5, jnp.float32)
    y, hl = rglru_scan_pallas(x, al, blk_s=blk_s, blk_w=blk_w, interpret=True)
    want = ref.rglru(x, al)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.array(y, np.float32),
                               np.array(want, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.array(hl, np.float32),
                               np.array(want[:, -1], np.float32),
                               atol=tol, rtol=tol)


def test_rglru_assoc_scan_with_h0():
    """Carried-state path: scan(x[:half]) then scan(x[half:], h0) == scan(x)."""
    B, S, W = 2, 64, 32
    x = jnp.asarray(RNG.normal(size=(B, S, W)), jnp.float32)
    al = jnp.asarray(-np.abs(RNG.normal(size=(B, S, W))) * 0.5, jnp.float32)
    full, _ = ops.rglru_scan(x, al, impl="chunked")
    h1, hf1 = ops.rglru_scan(x[:, :32], al[:, :32], impl="chunked")
    h2, _ = ops.rglru_scan(x[:, 32:], al[:, 32:], h0=hf1, impl="chunked")
    np.testing.assert_allclose(h2, full[:, 32:], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [(2, 128, 4, 16, 32, 32),
                                             (1, 256, 2, 8, 16, 64)])
def test_ssd_pallas_vs_ref(B, S, H, P, N, chunk):
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.normal(size=(B, S, H))) * 0.3 + 0.01, jnp.float32)
    A = jnp.asarray(-np.abs(RNG.normal(size=(H,))) - 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    y, hf = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    want = ref.ssd(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y, want, atol=5e-4, rtol=5e-4)


def test_ssd_chunked_vs_ref_and_state_handoff():
    B, S, H, P, N = 2, 96, 3, 8, 16
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.normal(size=(B, S, H))) * 0.3 + 0.01, jnp.float32)
    A = jnp.asarray(-np.abs(RNG.normal(size=(H,))) - 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    y, hf = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=32, impl="chunked")
    want = ref.ssd(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y, want, atol=5e-4, rtol=5e-4)
    # decode continuation from final state matches a longer ref scan
    y1, h1 = ops.ssd_decode_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                                 jnp.zeros((B, H, P, N)))
    np.testing.assert_allclose(y1, want[:, 0], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("n,slot_size,width", [(16, 256, 256), (8, 128, 300),
                                               (32, 64, 32)])
def test_burst_gather_pallas_vs_ref(n, slot_size, width):
    arena = jnp.asarray(RNG.integers(0, 256, size=(64, slot_size)), jnp.uint8)
    slots = jnp.asarray(RNG.permutation(64)[:n], jnp.int32)
    lens = jnp.asarray(RNG.integers(1, slot_size, size=(n,)), jnp.int32)
    got = burst_gather_pallas(arena, slots, lens, width, interpret=True)
    want = ref.burst_gather(arena, slots, lens, width)
    assert (np.array(got) == np.array(want)).all()


def test_attention_grad_paths():
    """Backward through the chunked path stays finite (remat inside scan)."""
    q, k, v = _qkv(1, 64, 2, 1, 16, jnp.float32)
    g = jax.grad(lambda q: ops.flash_attention(
        q, k, v, impl="chunked", q_chunk=32).sum())(q)
    assert np.isfinite(np.array(g)).all()


@pytest.mark.parametrize("B,S,H,Hkv,Dh,chunk",
                         [(2, 128, 4, 2, 16, 32), (1, 96, 6, 3, 8, 32),
                          (1, 100, 2, 1, 8, 16)])
def test_paired_causal_attention_vs_ref(B, S, H, Hkv, Dh, chunk):
    """Exact-flops pair-scheduled causal attention (EXPERIMENTS §Perf iter 6),
    including ragged sequence lengths and GQA."""
    q, k, v = _qkv(B, S, H, Hkv, Dh, jnp.float32)
    got = ops._paired_causal_attention(q, k, v, scale=Dh ** -0.5, chunk=chunk)
    want = ref.mha(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


def test_paired_attention_halves_flops():
    """The pair schedule must lower ~(n+1)/2n of the rectangle's dot flops."""
    import os
    from repro.parallel.hlo_counter import analyze
    q = jax.ShapeDtypeStruct((1, 1024, 2, 16), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 1024, 2, 16), jnp.float32)
    paired = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=True, impl="chunked", q_chunk=128))
    c1 = analyze(paired.lower(q, k, k).compile().as_text())
    os.environ["REPRO_NO_PAIRED"] = "1"
    try:
        full = jax.jit(lambda q, k, v: ops.flash_attention(
            q, k, v, causal=True, impl="chunked", q_chunk=127))
        c2 = analyze(full.lower(q, k, k).compile().as_text())
    finally:
        del os.environ["REPRO_NO_PAIRED"]
    ratio = c1.dot_flops / c2.dot_flops
    assert 0.4 < ratio < 0.65, ratio


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)],
                         ids=["causal", "window", "bidirectional"])
def test_pallas_attention_grad_matches_ref(causal, window):
    """Training through impl="pallas" differentiates the custom_vjp rule
    (Pallas forward, XLA backward): its gradients equal the oracle's."""
    q, k, v = _qkv(2, 128, 4, 2, 16, jnp.float32)
    w = jnp.asarray(RNG.normal(size=q.shape), jnp.float32)

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v) * w).sum()

    got = jax.grad(loss(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=causal, window=window, impl="pallas", q_chunk=32,
        interpret=True)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: ref.mha(
        q, k, v, causal=causal, window=window)), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)


def test_pallas_attention_grad_tiny_model(monkeypatch):
    """A tiny qwen3-family model: the loss gradient under the Pallas training
    rule (interpret mode) matches the one under the ref oracle."""
    from repro.data.pipeline import DataConfig, synth_tokens
    from repro.models import lm
    from repro.models.registry import get_smoke_config
    cfg = get_smoke_config("qwen3-1.7b").replace(param_dtype="float32",
                                                 compute_dtype="float32")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    batch = synth_tokens(cfg, DataConfig(seq_len=64, global_batch=2), 0, 1, 0)

    def grads(impl):
        monkeypatch.setenv("REPRO_FORCE_IMPL", impl)
        return jax.grad(lambda p: lm.train_loss(cfg, p, batch)[0])(params)

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("REPRO_FORCE_IMPL", "pallas")
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda p: lm.train_loss(cfg, p, batch)[0])(params))
    got, want = grads("pallas"), grads("ref")
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, r, atol=2e-5, rtol=2e-3)


def test_pallas_ops_run_per_batch_shard_under_a_mesh():
    """Under an active mesh each Pallas op goes through shard_map over the
    batch axes (GSPMD cannot partition a Mosaic kernel), with the same
    results as the plain call."""
    from repro.launch.mesh import make_smoke_mesh
    from repro.parallel.axes import axis_rules, pure_fsdp_rules
    q, k, v = _qkv(2, 128, 4, 2, 16, jnp.float32)
    cl = jnp.asarray([5, 128], jnp.int32)
    x = jnp.asarray(RNG.normal(size=(2, 64, 128)), jnp.float32)
    al = jnp.asarray(-np.abs(RNG.normal(size=(2, 64, 128))), jnp.float32)
    xs = jnp.asarray(RNG.normal(size=(2, 64, 2, 8)), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.normal(size=(2, 64, 2))) * 0.3, jnp.float32)
    A = jnp.asarray([-0.5, -1.0], jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(2, 64, 16)), jnp.float32)
    calls = [
        lambda: ops.flash_attention(q, k, v, impl="pallas", interpret=True),
        lambda: ops.decode_attention(q[:, 0], k, v, cl, impl="pallas",
                                     interpret=True),
        lambda: ops.rglru_scan(x, al, impl="pallas", interpret=True),
        lambda: ops.ssd_scan(xs, dt, A, Bm, Bm, chunk=32, impl="pallas",
                             interpret=True),
    ]
    for call in calls:
        want = call()
        with axis_rules(pure_fsdp_rules(), make_smoke_mesh(1)):
            assert "shard_map" in str(jax.make_jaxpr(call)())
            got = call()
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(g, w, atol=1e-6, rtol=1e-6)
