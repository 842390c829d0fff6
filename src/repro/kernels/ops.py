"""jit'd dispatch wrappers: model-layout in/out, kernel layout inside.

On CPU the kernels execute via ``interpret=True`` — the kernel body runs
in Python for correctness validation; on TPU the same ``pallas_call``
compiles to Mosaic.  ``force_reference`` escapes to the pure-jnp oracle
(used by the dry-run where interpret-mode pallas calls cannot lower for 512
fake devices).  A shape the kernel's blocks do not divide raises: a caller
that asked for the kernel never silently gets the oracle instead.

The SSD kernel is forward-only; its VJP differentiates the oracle
(``kref.ssd_scan_ref``) at the same inputs, re-running the oracle's forward
for its residuals.  A training step through the kernel therefore costs more
than one through the oracle alone: the kernel forward on top of the
oracle's forward and backward (under ``remat="full"`` the layer's recompute
adds a second kernel forward).  Only forward-only callers (prefill,
inference) can gain from the kernel until it has a backward of its own.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ssd_scan import ssd_scan_pallas

__all__ = ["flash_attention", "ssd_scan"]


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window",
                                             "block_q", "block_k",
                                             "force_reference"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    sliding_window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    force_reference: bool = False) -> jax.Array:
    """Model layout: q (B,S,H,hd), k/v (B,T,K,hd) -> (B,S,H,hd)."""
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    if force_reference:
        return kref.flash_attention_ref(q, k, v, causal=causal,
                                        sliding_window=sliding_window)
    if sq % min(block_q, sq) or sk % min(block_k, sk):
        raise ValueError(
            f"flash_attention: blocks ({block_q}, {block_k}) do not divide "
            f"the sequence lengths ({sq}, {sk})")
    scale = d ** -0.5
    qt = (q * scale).transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kh, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kh, sk, d)
    out = flash_attention_bhsd(
        qt, kt, vt, group=h // kh, causal=causal, window=sliding_window,
        block_q=block_q, block_k=block_k, interpret=_interpret(),
    )
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "force_reference"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bmat: jax.Array,
             cmat: jax.Array, *, chunk: int = 256,
             force_reference: bool = False):
    """Model layout: x (b,s,h,p), dt (b,s,h), a (h,), B/C (b,s,g,n).

    Returns (y (b,s,h,p) fp32, final_state (b,h,p,n) fp32).
    """
    s = x.shape[1]
    chunk = min(chunk, s)
    if force_reference:
        return kref.ssd_scan_ref(x, dt, a, bmat, cmat, chunk=chunk)
    if s % chunk:
        raise ValueError(
            f"ssd_scan: chunk {chunk} does not divide sequence length {s}")
    return _ssd_kernel(x, dt, a, bmat, cmat, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_kernel(x, dt, a, bmat, cmat, chunk):
    xk = x.transpose(0, 2, 1, 3)                       # (b,h,s,p)
    dtk = dt.transpose(0, 2, 1)[:, :, None, :]         # (b,h,1,s)
    bk = bmat.transpose(0, 2, 1, 3)                    # (b,g,s,n)
    ck = cmat.transpose(0, 2, 1, 3)
    y, state = ssd_scan_pallas(xk, dtk, a.astype(jnp.float32), bk, ck,
                               chunk=chunk, interpret=_interpret())
    return y.transpose(0, 2, 1, 3), state


def _ssd_kernel_fwd(x, dt, a, bmat, cmat, chunk):
    return _ssd_kernel(x, dt, a, bmat, cmat, chunk), (x, dt, a, bmat, cmat)


def _ssd_kernel_bwd(chunk, primals, cotangent):
    _, vjp = jax.vjp(
        lambda *args: kref.ssd_scan_ref(*args, chunk=chunk), *primals)
    return vjp(cotangent)


_ssd_kernel.defvjp(_ssd_kernel_fwd, _ssd_kernel_bwd)
