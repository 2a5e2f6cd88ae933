"""Batched probabilistic-increment kernels for F2P grid counters (DESIGN.md §6).

The device-side twin of ``repro.core.counters.CounterArray``: a flat array of
N-bit registers over a shared monotone estimate grid ``L[0..K-1]`` advances
from state ``k`` to ``k+1`` with probability ``p_k = 1/(L[k+1]-L[k])`` per
arrival (unbiased: expected estimate growth per arrival is exactly 1).

Two registered ops, both through :mod:`repro.kernels.dispatch`:

  ``counter_advance``   consume a per-cell arrival *budget* by the sequential
                        stochastic process, vectorized over all cells:
                        repeatedly draw the geometric sojourn of the current
                        state (inverse-CDF over uniforms — a counter-based
                        stream seeded per call from a ``jax.random`` key on
                        the xla backend, pre-drawn ``jax.random`` blocks on
                        the Pallas backends) and advance while the budget
                        covers it. Exact in distribution on the ``xla``
                        backend (a ``while_loop`` runs until every cell's
                        budget is spent); the Pallas kernel runs a *fixed*
                        number of sweeps and reports any unspent budget in
                        its ``leftover`` output instead of silently dropping
                        it.
  ``counter_estimate``  read estimates back: a gather through the decode LUT
                        (``L[state]`` — for F2P grids this is exactly the
                        format's ``payload_grid``, i.e. the same table the
                        8-bit dequantize LUT path uses).

Two exactness-preserving fast paths keep the sweep count small:

  * *unit runs*: wherever ``p_k == 1`` (gap <= 1 — the dense head of every
    integer grid) the sojourn is deterministically one arrival, so a whole
    run of such states is advanced in one vector step
    (``advance_tables`` precomputes run lengths).
  * geometric sojourns consume budget in expectation proportional to the
    gap, which grows along the grid — steady-state batches converge in a
    handful of sweeps.

All budget/sojourn arithmetic is float32: values stay exact below 2**24, so
per-call budgets (bounded by the ingest batch size) are exact; callers
feeding larger per-cell budgets must split them (``sketch.py`` does).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch
from repro.kernels.bits import fmix32

__all__ = ["advance_tables", "counter_advance_xla", "counter_advance_pallas",
           "counter_estimate_xla", "counter_estimate_pallas",
           "MAX_EXACT_BUDGET", "PALLAS_SWEEPS"]

# f32 integer-exactness ceiling for per-cell budgets (see module doc).
MAX_EXACT_BUDGET = 1 << 24

# Fixed sweep count of the Pallas kernel (static: it is the fori_loop trip
# count and the leading dim of the pre-drawn uniform block). Steady-state
# batches finish in ~4-8 sweeps; leftovers are returned, never dropped.
PALLAS_SWEEPS = 16


# ---------------------------------------------------------------------------
# Grid -> advance tables
# ---------------------------------------------------------------------------
def advance_tables(grid: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(p, unit_run, log_q) driving the advance process, length-K float32.

    ``p[k]``        advance probability out of state k (``p[K-1] = 0``: the
                    top state saturates).
    ``unit_run[k]`` length of the maximal run of consecutive states starting
                    at k with ``p == 1`` — the deterministic region a single
                    vector step can cross.
    ``log_q[k]``    ``log(1 - p[k])`` — the geometric inverse-CDF denominator
                    as a gather instead of a per-element transcendental
                    (0 where p is 0 or 1; both are special-cased).
    """
    g = np.asarray(grid, dtype=np.float64)
    gaps = np.diff(g)
    if np.any(gaps <= 0):
        raise ValueError("grid must be strictly increasing")
    K = len(g)
    p = np.zeros(K, dtype=np.float64)
    p[:-1] = np.minimum(1.0 / gaps, 1.0)
    unit = p == 1.0
    run = np.zeros(K, dtype=np.int64)
    for k in range(K - 2, -1, -1):
        run[k] = run[k + 1] + 1 if unit[k] else 0
    with np.errstate(divide="ignore"):
        log_q = np.where((p > 0) & (p < 1), np.log1p(-p), 0.0)
    return (p.astype(np.float32), run.astype(np.float32),
            log_q.astype(np.float32))


def _sojourn(u: jnp.ndarray, p: jnp.ndarray, log_q: jnp.ndarray) -> jnp.ndarray:
    """Geometric sojourn draw by inverse CDF: T = ceil(log u / log(1-p)).

    ``p == 1`` -> exactly 1; ``p == 0`` (saturated top state) -> +inf so the
    comparison against any finite budget fails and the cell parks."""
    t = jnp.ceil(jnp.log(u) / log_q)
    t = jnp.where(p >= 1.0, 1.0, t)
    t = jnp.where(p <= 0.0, jnp.inf, t)
    return jnp.maximum(t, 1.0)


def _sweep(state, rem, u, p_lut, run_lut, logq_lut, kmax, take=jnp.take):
    """One vector step: cross the unit run, then one geometric sojourn.
    ``take(lut, state)`` reads a table (a gather; the Pallas body passes a
    Mosaic-lowerable select loop that returns the same values)."""
    run = jnp.minimum(rem, take(run_lut, state))
    state = state + run.astype(jnp.int32)
    rem = rem - run
    need = _sojourn(u, take(p_lut, state), take(logq_lut, state))
    adv = need <= rem
    state = jnp.where(adv, jnp.minimum(state + 1, kmax), state)
    # a sojourn exceeding the budget means no advance happens within this
    # batch — the cell is done (memorylessness makes discarding the partial
    # progress exact); likewise a saturated cell (need = inf) parks
    rem = jnp.where(adv, rem - need, 0.0)
    return state, rem


def _hash_uniform(seed: jnp.ndarray, sweep: jnp.ndarray,
                  lanes: jnp.ndarray) -> jnp.ndarray:
    """Counter-based uniform stream on (0, 1): murmur3-avalanched function of
    (seed, sweep counter, lane index).

    The per-sweep RNG of the advance loop. A threefry ``jax.random.uniform``
    per sweep costs more than the whole rest of the sweep on CPU; this is the
    stateless-counter construction hardware PRNGs use (cf.
    ``pltpu.prng_random_bits`` on the Pallas path), seeded per advance call
    from a ``jax.random`` key so streams never collide across batches."""
    x = fmix32(lanes ^ (sweep * jnp.uint32(0x9E3779B1)) ^ seed)
    # 24 mantissa-exact bits, offset by half an ulp -> strictly inside (0, 1)
    return ((x >> 8).astype(jnp.float32) + 0.5) * jnp.float32(2.0 ** -24)


# ---------------------------------------------------------------------------
# XLA backend: while_loop until every budget is spent (exact in distribution)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("kmax",))
def _advance_xla_jit(state, budget, p_lut, run_lut, logq_lut, key, *,
                     kmax: int):
    seed = jax.random.bits(key, (), jnp.uint32)
    shape = state.shape
    lanes = jnp.arange(state.size, dtype=jnp.uint32).reshape(shape)

    def cond(carry):
        _, rem, _ = carry
        return jnp.any(rem > 0)

    def body(carry):
        state, rem, sweep = carry
        u = _hash_uniform(seed, sweep, lanes)
        state, rem = _sweep(state, rem, u, p_lut, run_lut, logq_lut, kmax)
        return state, rem, sweep + jnp.uint32(1)

    state, rem, _ = jax.lax.while_loop(
        cond, body, (state, budget.astype(jnp.float32), jnp.uint32(0)))
    return state, jnp.zeros_like(rem)


def counter_advance_xla(state, budget, p_lut, run_lut, logq_lut, key):
    """Exact batched advance. Returns ``(new_state, leftover)``; leftover is
    identically zero here (the loop runs to completion)."""
    kmax = int(p_lut.shape[0]) - 1
    return _advance_xla_jit(jnp.asarray(state), jnp.asarray(budget),
                            jnp.asarray(p_lut), jnp.asarray(run_lut),
                            jnp.asarray(logq_lut), key, kmax=kmax)


# ---------------------------------------------------------------------------
# Pallas backend: fixed-sweep kernel over the whole (rows, width) register
# array (sketch depths are below 8 rows, so Mosaic needs whole-array blocks),
# pre-drawn uniforms, tables in SMEM. The TPU has no vector gather: a table
# read is a loop of selects over the K table entries.
# ---------------------------------------------------------------------------
def _smem_take(lut_ref, idx):
    """``lut[idx]`` for an SMEM table ref: one select per table entry."""
    def body(k, acc):
        return jnp.where(idx == k, lut_ref[k], acc)

    return jax.lax.fori_loop(0, lut_ref.shape[0], body,
                             jnp.zeros(idx.shape, jnp.float32))


def _advance_kernel(sweeps, kmax, state_ref, budget_ref, u_ref, p_ref,
                    run_ref, logq_ref, out_state_ref, out_left_ref):
    state = state_ref[...].astype(jnp.int32)    # (rows, width)
    rem = budget_ref[...]                       # (rows, width) f32

    def step(t, carry):
        state, rem = carry
        return _sweep(state, rem, u_ref[t], p_ref, run_ref, logq_ref, kmax,
                      take=_smem_take)

    state, rem = jax.lax.fori_loop(0, sweeps, step, (state, rem))
    out_state_ref[...] = state
    out_left_ref[...] = rem


@functools.partial(jax.jit,
                   static_argnames=("sweeps", "kmax", "interpret"))
def _advance_pallas_jit(state, budget, u, p_lut, run_lut, logq_lut, *,
                        sweeps: int, kmax: int, interpret: bool):
    rows, width = state.shape
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_advance_kernel, sweeps, kmax),
        in_specs=[pl.BlockSpec(), pl.BlockSpec(), pl.BlockSpec(),
                  smem, smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((rows, width), jnp.int32),
            jax.ShapeDtypeStruct((rows, width), jnp.float32),
        ],
        interpret=interpret,
    )(state, budget, u, p_lut, run_lut, logq_lut)


def counter_advance_pallas(state, budget, p_lut, run_lut, logq_lut, key, *,
                           sweeps: int = PALLAS_SWEEPS,
                           interpret: bool | None = None):
    """Fixed-sweep Pallas advance over a (rows, width) register array.

    Uniforms are drawn up front with ``jax.random`` (shape
    ``(sweeps, rows, width)``) and streamed through the kernel, one slice per
    sweep — on a real TPU deployment this slot is where
    ``pltpu.prng_random_bits`` takes over. Budget a cell cannot spend within
    ``sweeps`` sweeps comes back in ``leftover`` — callers either re-issue it
    (``sketch.py`` folds it into the next batch) or treat it as a truncation
    diagnostic."""
    if interpret is None:
        interpret = dispatch.pallas_variant() == dispatch.PALLAS_INTERPRET
    state = jnp.asarray(state)
    if state.ndim == 1:
        st, lf = counter_advance_pallas(state[None, :], budget[None, :],
                                        p_lut, run_lut, logq_lut, key,
                                        sweeps=sweeps, interpret=interpret)
        return st[0], lf[0]
    rows, width = state.shape
    u = jax.random.uniform(key, (sweeps, rows, width), dtype=jnp.float32,
                           minval=jnp.float32(np.finfo(np.float32).tiny))
    kmax = int(p_lut.shape[0]) - 1
    return _advance_pallas_jit(state, jnp.asarray(budget, jnp.float32), u,
                               jnp.asarray(p_lut), jnp.asarray(run_lut),
                               jnp.asarray(logq_lut),
                               sweeps=sweeps, kmax=kmax,
                               interpret=bool(interpret))


# ---------------------------------------------------------------------------
# Estimate read: decode-LUT gather
# ---------------------------------------------------------------------------
@jax.jit
def counter_estimate_xla(state, grid_lut):
    """Estimates ``L[state]`` as a fused LUT gather (cf. ``dequantize_lut``)."""
    return jnp.take(jnp.asarray(grid_lut, jnp.float32),
                    jnp.asarray(state, jnp.int32))


def _estimate_kernel(state_ref, grid_ref, out_ref):
    out_ref[...] = _smem_take(grid_ref, state_ref[...].astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _estimate_pallas_jit(state, grid_lut, *, interpret: bool):
    rows, width = state.shape
    return pl.pallas_call(
        _estimate_kernel,
        in_specs=[pl.BlockSpec(), pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=jax.ShapeDtypeStruct((rows, width), jnp.float32),
        interpret=interpret,
    )(state, grid_lut)


def counter_estimate_pallas(state, grid_lut, *, interpret: bool | None = None):
    if interpret is None:
        interpret = dispatch.pallas_variant() == dispatch.PALLAS_INTERPRET
    state = jnp.asarray(state, jnp.int32)
    if state.ndim == 1:
        return counter_estimate_pallas(state[None, :], grid_lut,
                                       interpret=interpret)[0]
    return _estimate_pallas_jit(state, jnp.asarray(grid_lut, jnp.float32),
                                interpret=bool(interpret))


# ---------------------------------------------------------------------------
# Registry wiring (repro.kernels.dispatch)
# ---------------------------------------------------------------------------
dispatch.register("counter_advance", dispatch.XLA)(counter_advance_xla)


@dispatch.register("counter_advance", dispatch.PALLAS)
def _advance_pallas_compiled(state, budget, p_lut, run_lut, logq_lut, key,
                             **kw):
    return counter_advance_pallas(state, budget, p_lut, run_lut, logq_lut,
                                  key, interpret=False, **kw)


@dispatch.register("counter_advance", dispatch.PALLAS_INTERPRET)
def _advance_pallas_interp(state, budget, p_lut, run_lut, logq_lut, key,
                           **kw):
    return counter_advance_pallas(state, budget, p_lut, run_lut, logq_lut,
                                  key, interpret=True, **kw)


dispatch.register("counter_estimate", dispatch.XLA)(counter_estimate_xla)


@dispatch.register("counter_estimate", dispatch.PALLAS)
def _estimate_pallas_compiled(state, grid_lut):
    return counter_estimate_pallas(state, grid_lut, interpret=False)


@dispatch.register("counter_estimate", dispatch.PALLAS_INTERPRET)
def _estimate_pallas_interp(state, grid_lut):
    return counter_estimate_pallas(state, grid_lut, interpret=True)
