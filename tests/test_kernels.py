"""Pallas kernel validation: shape/dtype sweeps + hypothesis, vs jnp oracles.

All kernels run through the fused-op registry (``repro.kernels.api``) in
interpret mode on CPU (the kernel body executes in Python, so the
block/mask/online-softmax logic is what is being validated).  Registry-wide
forward/VJP parity and launch accounting live in test_fused_api.py; this file
keeps the deep per-op shape/dtype/feature sweeps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import api
from repro.kernels.flash_attention import flash_attention_ref
from repro.kernels.mvr_update import mvr_update_ref
from repro.kernels.rms_norm import rms_norm_ref
from repro.kernels.wkv_chunk import wkv_ref


def icall(name, *args, **static):
    """api.call with the interpret-mode kernel forced (CPU default is ref)."""
    with api.dispatch_mode("interpret"):
        return api.call(name, *args, **static)


def _qkv(key, b, s, h, kh, d, dtype):
    """q scaled by 1/sqrt(d), as the kernel's callers hand it over."""
    ks = jax.random.split(key, 3)
    q = (jax.random.normal(ks[0], (b, s, h, d)) * d ** -0.5).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kh, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kh, d)).astype(dtype)
    return q, k, v


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------- flash attn
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,kh,d,window,softcap,causal",
    [
        (1, 128, 2, 2, 64, None, None, True),     # MHA causal
        (2, 256, 4, 2, 64, None, None, True),     # GQA
        (1, 256, 4, 1, 128, None, None, True),    # MQA, d=128
        (1, 256, 2, 2, 64, 128, None, True),      # sliding window
        (1, 256, 2, 2, 64, 64, 50.0, True),       # window + softcap (gemma2 local)
        (1, 128, 2, 2, 64, None, 30.0, True),     # softcap
        (1, 128, 2, 2, 64, None, None, False),    # bidirectional (encoder)
        (1, 384, 2, 2, 256, None, None, True),    # gemma2 head_dim 256
    ],
)
def test_flash_attention_sweep(b, s, h, kh, d, window, softcap, causal, dtype):
    q, k, v = _qkv(jax.random.key(42), b, s, h, kh, d, dtype)
    out = icall(
        "flash_attention", q, k, v,
        causal=causal, sliding_window=window, softcap=softcap,
    )
    ref = flash_attention_ref(q, k, v, causal=causal, sliding_window=window, softcap=softcap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


def test_flash_attention_nonsquare_blocks():
    """A sequence of several tiles (three of 512: skipped, diagonal and full
    tiles, each q tile seeing a different number of kv tiles) is covered."""
    q, k, v = _qkv(jax.random.key(0), 1, 1536, 2, 2, 64, jnp.float32)
    out = icall("flash_attention", q, k, v, causal=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_attention_grad_matches_ref():
    """The kernel's own backward (dq and dkv kernels) matches jnp autodiff
    of the oracle."""
    q, k, v = _qkv(jax.random.key(1), 1, 128, 2, 2, 64, jnp.float32)

    def f_kernel(q, k, v):
        return (icall("flash_attention", q, k, v, causal=True) ** 2).sum()

    def f_ref(q, k, v):
        return (flash_attention_ref(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "s,h,kh,d,window,softcap,dtype",
    [
        (256, 32, 4, 128, None, None, jnp.float32),     # Yi-9B heads, causal
        (512, 32, 4, 128, None, None, jnp.float32),
        (512, 32, 4, 128, None, None, jnp.bfloat16),
        (1024, 8, 4, 256, 256, 50.0, jnp.float32),      # Gemma-2 local layer
    ],
)
def test_flash_attention_matches_sdpa(s, h, kh, d, window, softcap, dtype):
    """The kernel (q scaled by the caller) against the model's ``_sdpa``:
    output and the gradients in q, k and v."""
    from repro.models.attention import AttentionConfig, _sdpa

    cfg = AttentionConfig(d_model=h * d, n_heads=h, n_kv_heads=kh, head_dim=d,
                          sliding_window=window, attn_softcap=softcap)
    ks = jax.random.split(jax.random.key(7), 4)
    q, k, v, ct = (jax.random.normal(kk, shape).astype(dtype) for kk, shape in zip(
        ks, [(1, s, h, d), (1, s, kh, d), (1, s, kh, d), (1, s, h, d)]))
    pos = jnp.arange(s)[None]

    def kernel(q, k, v):
        qs = (q.astype(jnp.float32) * d ** -0.5).astype(q.dtype)
        return icall("flash_attention", qs, k, v, causal=True,
                     sliding_window=window, softcap=softcap)

    out, vjp = jax.vjp(kernel, q, k, v)
    want, vjp_want = jax.vjp(lambda q, k, v: _sdpa(cfg, q, k, v, pos, pos), q, k, v)
    tol = TOL[dtype] if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-5)
    for got, ref in zip((out, *vjp(ct)), (want, *vjp_want(ct))):
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref, np.float32), **tol)


def _attention_counts(cfg, seq, batch, mode):
    """``api.call_counts`` of one traced gradient of ``cfg``'s loss."""
    from repro.models import Model

    model = Model(cfg)
    api.reset_counters()
    with api.dispatch_mode(mode):
        jax.eval_shape(jax.grad(model.loss), model.param_shapes(),
                       model.input_specs(seq, batch))
    counts = api.call_counts()
    return counts.get("attn.flash", 0), counts.get("attn.sdpa", 0)


def _published(arch, **kw):
    import dataclasses

    from repro.configs import get_config

    cfg = get_config(arch)
    return dataclasses.replace(cfg, **{"n_layers": len(cfg.block_unit), **kw})


@pytest.mark.parametrize("seq,batch", [(2048, 2), (512, 1), (2048, 1)])
def test_attention_dispatch_benchmark_cells_take_the_kernel(seq, batch):
    """Yi-9B at the benchmark's widths and each cell's per-node shapes
    (s2k, s512, the ring's node) runs the kernel on the TPU, never _sdpa."""
    cfg = _published("yi-9b", n_layers=2, vocab_size=8000)
    flash, sdpa = _attention_counts(cfg, seq, batch, "kernel")
    assert flash > 0 and sdpa == 0


@pytest.mark.parametrize(
    "arch,seq,mode,impl,takes",
    [
        ("yi-9b", 2048, "ref", "xla", False),           # off the TPU
        ("yi-9b", 1000, "kernel", "xla", False),        # no whole tiles
        ("yi-9b", 256, "ref", "pallas", True),          # forced: interpret mode
        ("gemma2-2b", 1024, "kernel", "xla", True),     # window 4096, softcap 50
        ("minitron-8b", 2048, "kernel", "xla", True),
        ("zamba2-7b", 1024, "kernel", "xla", False),    # head_dim 112
        ("hubert-xlarge", 1024, "kernel", "xla", False),  # bidirectional
        ("qwen2-vl-2b", 1024, "kernel", "xla", False),  # M-RoPE positions
    ],
)
def test_attention_dispatch_follows_what_it_sees(arch, seq, mode, impl, takes):
    flash, sdpa = _attention_counts(_published(arch, attn_impl=impl), seq, 1, mode)
    assert (flash > 0, sdpa > 0) == (takes, not takes)


@settings(max_examples=8, deadline=None)
@given(
    s=st.sampled_from([128, 256]),
    h=st.sampled_from([1, 2, 4]),
    d=st.sampled_from([64, 128]),
    window=st.sampled_from([None, 64, 128]),
)
def test_flash_attention_property(s, h, d, window):
    q, k, v = _qkv(jax.random.key(s * h * d), 1, s, h, h, d, jnp.float32)
    out = icall("flash_attention", q, k, v, causal=True, sliding_window=window)
    ref = flash_attention_ref(q, k, v, causal=True, sliding_window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- rms norm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 128), (2, 64, 256), (1, 3, 5, 512), (256, 1024)])
@pytest.mark.parametrize("plus_one", [False, True])
def test_rms_norm_sweep(shape, dtype, plus_one):
    # (1, 3, 5, 512) has 15 rows: exercises the pad-rows-to-block path that
    # replaced the old divide-by-halving block selection
    x = jax.random.normal(jax.random.key(0), shape).astype(dtype)
    w = jax.random.normal(jax.random.key(1), shape[-1:])
    out = icall("rms_norm", x, w, eps=1e-6, plus_one=plus_one)
    ref = rms_norm_ref(x, w, 1e-6, plus_one)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


def test_rms_norm_grad():
    x = jax.random.normal(jax.random.key(2), (16, 128))
    w = jax.random.normal(jax.random.key(3), (128,))
    g1 = jax.grad(lambda x_: icall("rms_norm", x_, w).sum())(x)
    g2 = jax.grad(lambda x_: rms_norm_ref(x_, w).sum())(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- mvr update
def _mvr(gn, v, go, alpha):
    with api.dispatch_mode("interpret"):
        return api.tree_apply("mvr_update", gn, v, go, scalars=(alpha,))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1024,), (512, 128), (3, 7, 11)])
@pytest.mark.parametrize("alpha", [0.0, 0.05, 1.0])
def test_mvr_update_sweep(shape, dtype, alpha):
    ks = jax.random.split(jax.random.key(0), 3)
    gn = jax.random.normal(ks[0], shape).astype(dtype)
    v = jax.random.normal(ks[1], shape).astype(dtype)
    go = jax.random.normal(ks[2], shape).astype(dtype)
    out = _mvr(gn, v, go, alpha)
    ref = mvr_update_ref(gn, v, go, alpha)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 4096), alpha=st.floats(0.0, 1.0))
def test_mvr_update_property(n, alpha):
    """EVERY size runs on the kernel path now (lane padding; no oracle
    fallback for ragged buffers)."""
    ks = jax.random.split(jax.random.key(n), 3)
    gn, v, go = (jax.random.normal(k, (n,)) for k in ks)
    api.reset_counters()
    out = _mvr(gn, v, go, alpha)
    assert api.launch_counts() == {"mvr_update": 1}, n
    ref = mvr_update_ref(gn, v, go, alpha)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)


def test_mvr_alpha_one_is_sgd():
    """alpha=1 collapses MVR to the plain gradient (DSE-SGD reduction)."""
    ks = jax.random.split(jax.random.key(5), 3)
    gn, v, go = (jax.random.normal(k, (512,)) for k in ks)
    np.testing.assert_allclose(
        np.asarray(_mvr(gn, v, go, 1.0)), np.asarray(gn), rtol=1e-6, atol=1e-6
    )


# ---------------------------------------------------------------- wkv chunk
def _wkv_inputs(key, b, s, h, p, decay_mag=1.0, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    r = (jax.random.normal(ks[0], (b, s, h, p)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (b, s, h, p)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (b, s, h, p)) * 0.5).astype(dtype)
    # log-decay magnitude ~ decay_mag (trained RWKV channels are mostly mild,
    # |logw| << 1; the fp32 clamp bounds chunk_len * |logw| <~ 25)
    logw = -decay_mag * jnp.exp(jax.random.normal(ks[3], (b, s, h, p)) * 0.3)
    return r, k, v, logw.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,p,chunk",
    [
        (1, 32, 1, 16, 16),
        (2, 64, 2, 32, 16),
        (1, 64, 4, 64, 16),     # production head size
        (1, 64, 1, 32, 32),     # longer chunk, mild decay
    ],
)
def test_wkv_chunk_sweep(b, s, h, p, chunk, dtype):
    # chunk > 16 is only numerically safe for mild decay (clamp envelope:
    # chunk * |logw| < ~25) — measured in EXPERIMENTS A1
    r, k, v, logw = _wkv_inputs(jax.random.key(7), b, s, h, p,
                                decay_mag=0.3 if chunk > 16 else 1.0, dtype=dtype)
    y1, s1 = icall("wkv_chunk", r, k, v, logw, chunk=chunk)
    y2, s2 = wkv_ref(r, k, v, logw)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2, np.float32), **tol)


def test_wkv_chunk_grad_matches_oracle():
    r, k, v, logw = _wkv_inputs(jax.random.key(9), 1, 32, 1, 16)

    def f_kernel(r, k, v, w):
        y, s = icall("wkv_chunk", r, k, v, w, chunk=16)
        return (y ** 2).sum() + (s ** 2).sum()

    def f_ref(r, k, v, w):
        y, s = wkv_ref(r, k, v, w)
        return (y ** 2).sum() + (s ** 2).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2, 3))(r, k, v, logw)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2, 3))(r, k, v, logw)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


@settings(max_examples=6, deadline=None)
@given(s=st.sampled_from([32, 64]), p=st.sampled_from([16, 32]))
def test_wkv_chunk_property(s, p):
    r, k, v, logw = _wkv_inputs(jax.random.key(s * p), 1, s, 2, p)
    y1, s1 = icall("wkv_chunk", r, k, v, logw, chunk=16)
    y2, s2 = wkv_ref(r, k, v, logw)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4, atol=2e-5)
