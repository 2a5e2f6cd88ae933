"""Backend dispatch registry for the F2P kernel ops (DESIGN.md §3.4).

One explicit selection point for every kernel entry in the repo, replacing
the former scattered ``interpret=not _on_tpu()`` defaults in
``f2p_quant.py`` / ``f2p_matmul.py``.

Backends:

  ``pallas``            compiled Pallas kernels — the TPU hot path
  ``pallas_interpret``  Pallas in interpreter mode — kernel debugging / CI
                        parity runs on CPU; slow, never a default
  ``xla``               the same tile math as plain jnp under jit — fuses into
                        surrounding HLO; the host/CPU default

Resolution order when no backend is requested:

  1. ``F2P_BACKEND`` env var (explicit operator override, e.g. CI matrices)
  2. TPU available and the op has a Pallas kernel -> ``pallas``, inside a
     jit trace or not: the jitted serving round must run the kernels, not
     their XLA twins
  3. otherwise -> ``xla``

Callers that must not get a kernel pin ``backend="xla"`` themselves (e.g.
``optim.compress`` inside ``shard_map``, which has no replication rule for
a ``pallas_call``).

Ops register per-backend implementations with :func:`register`; callers go
through :func:`lookup`, which resolves the backend *and* validates that the
op actually has an implementation for it. Registered ops:

  ``quantize`` / ``dequantize``            block-scaled F2P tensor codecs
                                           (``kernels/f2p_quant.py``)
  ``quantize_packed`` / ``dequantize_packed``  the same codecs with the n-bit
                                           field pack/unpack fused into the
                                           kernel body — packed QTensor
                                           storage (DESIGN.md §9)
  ``dequant_matmul`` / ``dequant_matmul_packed``  fused dequantize-matmul on
                                           byte-aligned / bit-packed weight
                                           streams (``kernels/f2p_matmul.py``)
  ``attention_packed``                     fused flash-style online-softmax
                                           attention streaming bit-packed KV
                                           word tiles with in-register
                                           unpack + decode
                                           (``kernels/f2p_attention.py``)
  ``attention_paged``                      the same fused attention reading
                                           KV word tiles THROUGH a per-row
                                           page table straight from the pool
                                           slabs — no dense per-request KV
                                           row exists
                                           (``kernels/f2p_attention.py``)
  ``counter_advance`` / ``counter_estimate``  batched probabilistic grid-counter
                                           updates + decode-LUT estimate reads
                                           for the sketch engine
                                           (``kernels/f2p_counter.py``)
"""
from __future__ import annotations

import os
from typing import Callable

import jax

__all__ = ["PALLAS", "PALLAS_INTERPRET", "XLA", "BACKENDS", "register",
           "implementations", "resolve_backend", "pallas_variant", "lookup"]

PALLAS = "pallas"
PALLAS_INTERPRET = "pallas_interpret"
XLA = "xla"
BACKENDS = (PALLAS, PALLAS_INTERPRET, XLA)

# accepted spellings -> canonical name
_ALIASES = {
    "pallas-interpret": PALLAS_INTERPRET,
    "interpret": PALLAS_INTERPRET,
    "jit": XLA,
    "tile_math": XLA,
}

_REGISTRY: dict[str, dict[str, Callable]] = {}


def register(op: str, backend: str):
    """Decorator: register ``fn`` as the ``backend`` implementation of ``op``."""
    backend = _canonical(backend)

    def deco(fn: Callable) -> Callable:
        _REGISTRY.setdefault(op, {})[backend] = fn
        return fn

    return deco


def implementations(op: str) -> dict[str, Callable]:
    """Registered backend -> implementation map for ``op`` (a copy)."""
    return dict(_REGISTRY.get(op, {}))


def _canonical(backend: str) -> str:
    b = _ALIASES.get(backend, backend)
    if b not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS} (or aliases {tuple(_ALIASES)})")
    return b


def pallas_variant() -> str:
    """Which Pallas flavor this process can actually run: compiled on TPU,
    interpreter everywhere else."""
    return PALLAS if jax.default_backend() == "tpu" else PALLAS_INTERPRET


def resolve_backend(backend: str | None = None, *, op: str | None = None) -> str:
    """Resolve a backend name. ``None`` applies the policy in the module doc;
    with ``op`` given, also require that the op implements the result."""
    if backend is None:
        backend = os.environ.get("F2P_BACKEND") or None
    if backend is None:
        has_kernel = op is None or PALLAS in _REGISTRY.get(op, {})
        backend = (PALLAS if jax.default_backend() == "tpu" and has_kernel
                   else XLA)
    backend = _canonical(backend)
    if op is not None:
        impls = _REGISTRY.get(op, {})
        if backend not in impls:
            raise ValueError(
                f"op {op!r} has no {backend!r} implementation "
                f"(available: {sorted(impls) or 'none'})")
    return backend


def lookup(op: str, backend: str | None = None) -> tuple[str, Callable]:
    """(resolved backend name, implementation) for ``op``."""
    b = resolve_backend(backend, op=op)
    return b, _REGISTRY[op][b]
