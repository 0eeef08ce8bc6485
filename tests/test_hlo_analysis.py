"""Loop-aware HLO analyzer tests (the roofline's measurement instrument)."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze_module, parse_shape_bytes


def test_parse_shape_bytes():
    assert parse_shape_bytes("f32[4,8]") == 128
    assert parse_shape_bytes("bf16[2,3,5]") == 60
    assert parse_shape_bytes("(f32[4], s32[2])") == 24
    assert parse_shape_bytes("pred[]") == 1
    assert parse_shape_bytes("f32[0]") == 0


def test_scan_trip_count_exact():
    """13-iteration scan of 8x8 matmuls must report exactly 13 * 2*8^3 flops
    (XLA's own cost_analysis reports ~1 iteration — the bug this module
    exists to fix)."""

    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), ()

        out, _ = jax.lax.scan(body, x, w)
        return out

    compiled = (
        jax.jit(f)
        .lower(
            jax.ShapeDtypeStruct((8, 8), jnp.float32),
            jax.ShapeDtypeStruct((13, 8, 8), jnp.float32),
        )
        .compile()
    )
    ours = analyze_module(compiled.as_text())
    assert ours.flops == 13 * 2 * 8 * 8 * 8
    assert compiled.cost_analysis()["flops"] < ours.flops / 6  # the undercount we correct


def test_nested_scan_multiplies():
    def f(x, w):
        def outer(c, _):
            def inner(ci, wi):
                return ci @ wi, ()

            c2, _ = jax.lax.scan(inner, c, w)
            return c2, ()

        out, _ = jax.lax.scan(outer, x, None, length=3)
        return out

    compiled = (
        jax.jit(f)
        .lower(
            jax.ShapeDtypeStruct((4, 4), jnp.float32),
            jax.ShapeDtypeStruct((5, 4, 4), jnp.float32),
        )
        .compile()
    )
    ours = analyze_module(compiled.as_text())
    assert ours.flops == 3 * 5 * 2 * 4 * 4 * 4, ours.flops


def test_dot_with_batch_dims():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    compiled = (
        jax.jit(f)
        .lower(
            jax.ShapeDtypeStruct((2, 8, 16), jnp.float32),
            jax.ShapeDtypeStruct((2, 16, 4), jnp.float32),
        )
        .compile()
    )
    ours = analyze_module(compiled.as_text())
    assert ours.flops == 2 * 2 * 8 * 16 * 4


def test_hbm_includes_fusion_boundary():
    def f(x):
        return jnp.tanh(x) * 2 + 1

    compiled = jax.jit(f).lower(jax.ShapeDtypeStruct((1024,), jnp.float32)).compile()
    ours = analyze_module(compiled.as_text())
    # at least read + write of the 4 KB buffer
    assert ours.hbm_bytes >= 2 * 4096
