"""Tests for the trip-count-aware HLO analyzer that feeds the roofline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_dot_flops_exact():
    x = jnp.ones((32, 128), jnp.float32)
    w = jnp.ones((128, 64), jnp.float32)
    a = analyze_hlo(_compile_text(lambda x, w: x @ w, x, w))
    assert a["flops"] == 2 * 32 * 128 * 64


def test_scan_flops_match_unrolled():
    def body(x, w):
        return jnp.tanh(x @ w), None

    W = jnp.ones((8, 256, 256), jnp.bfloat16)
    x = jnp.ones((64, 256), jnp.bfloat16)
    a_s = analyze_hlo(_compile_text(
        lambda x, W: jax.lax.scan(body, x, W)[0], x, W))

    def unrolled(x, W):
        for i in range(8):
            x, _ = body(x, W[i])
        return x

    a_u = analyze_hlo(_compile_text(unrolled, x, W))
    assert a_s["flops"] == a_u["flops"] == 2 * 64 * 256 * 256 * 8


def test_grad_of_scan_counts_bwd_loop():
    def body(x, w):
        return jnp.tanh(x @ w), None

    W = jnp.ones((8, 256, 256), jnp.bfloat16)
    x = jnp.ones((64, 256), jnp.bfloat16)

    def loss(x, W):
        return jnp.sum(jax.lax.scan(body, x, W)[0] ** 2)

    a = analyze_hlo(_compile_text(jax.grad(loss, argnums=1), x, W))
    assert a["flops"] == 3 * 2 * 64 * 256 * 256 * 8  # fwd + 2 bwd matmuls


def test_nested_scan_multiplies():
    def inner(c, x):
        return c @ x, None

    def outer(c, xs):
        def b(c, _):
            c2, _ = jax.lax.scan(inner, c, xs)
            return c2, None

        return jax.lax.scan(b, c, None, length=5)[0]

    c = jnp.ones((64, 64), jnp.float32)
    xs = jnp.ones((3, 64, 64), jnp.float32)
    a = analyze_hlo(_compile_text(outer, c, xs))
    assert a["flops"] == 5 * 3 * 2 * 64 * 64 * 64


def test_scan_memory_not_billed_full_buffer():
    """dynamic-slice / DUS inside loops charge slices, not whole buffers."""

    def body(c, x):
        return c + x, c.sum()

    xs = jnp.ones((1024, 64, 64), jnp.float32)  # 16 MB stacked input
    c = jnp.ones((64, 64), jnp.float32)
    a = analyze_hlo(_compile_text(lambda c, xs: jax.lax.scan(body, c, xs), c, xs))
    # per-step traffic is O(slice)=16KB; billing the full 16MB xs per step
    # would give >16 GB. Generous bound: < 0.5 GB total.
    assert a["hbm_bytes"] < 0.5e9, a["hbm_bytes"] / 1e9


def test_collectives_inside_scan_multiplied():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))

    def step(c, x):
        return c + jax.lax.psum(x, "d"), None

    def f(c, xs):
        return jax.lax.scan(step, c, xs)[0]

    g = shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                  check_vma=False)
    c = jnp.ones((64, 64), jnp.float32)
    xs = jnp.ones((7, 64, 64), jnp.float32)
    txt = jax.jit(g).lower(c, xs).compile().as_text()
    a = analyze_hlo(txt, n_devices=1)
    ar = a["per_op"].get("all-reduce", {"count": 0})
    assert ar["count"] == 7  # one per scan step, multiplied by trip count


def test_while_trip_count_parsing():
    def f(x):
        def cond(s):
            return s[0] < 23

        def body(s):
            return (s[0] + 1, s[1] @ s[1])

        return jax.lax.while_loop(cond, body, (0, x))[1]

    x = jnp.ones((32, 32), jnp.float32)
    a = analyze_hlo(_compile_text(f, x))
    # dynamic while (no known trip count): falls back to cond constant 23
    assert a["flops"] == pytest.approx(23 * 2 * 32**3, rel=0.1)
