"""Mamba2 mixer: state-space duality (SSD) with chunked scan.

Layout follows the Mamba2 paper (arXiv:2405.21060): per-head scalar decay
``a_t = exp(-exp(A_log) * dt_t)``, grouped B/C (GQA-analogue), short causal
depthwise conv over the (x, B, C) stream, gated RMSNorm, out projection.

``ssd_reference`` is the pure-jnp oracle (chunk-quadratic + inter-chunk
state recurrence via lax.scan); the Pallas kernel in
``repro.kernels.ssd_scan`` accelerates the same computation and is verified
against it.  ``ssd_decode_step`` is the O(1) recurrent form used for
decoding (the long_500k path).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.api import ModelConfig, SSMConfig

__all__ = ["init_ssm", "ssm_mixer", "ssd_reference", "SSMState", "init_ssm_state",
           "ssm_decode_step"]


def _dims(d_model: int, s: SSMConfig):
    d_inner = s.expand * d_model
    n_heads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.state_dim
    return d_inner, n_heads, conv_dim


def init_ssm(rng, d_model: int, s: SSMConfig, dtype) -> dict:
    d_inner, n_heads, conv_dim = _dims(d_model, s)
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    proj_out = 2 * d_inner + 2 * s.n_groups * s.state_dim + n_heads  # z,x,B,C,dt
    scale = d_model ** -0.5
    return {
        "in_proj": (jax.random.normal(k1, (d_model, proj_out)) * scale).astype(dtype),
        "conv_w": (jax.random.normal(k2, (s.conv_width, conv_dim)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.zeros((n_heads,), jnp.float32),      # A = -exp(A_log) = -1
        "D": jnp.ones((n_heads,), jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "norm_w": jnp.zeros((d_inner,), dtype),
        "out_proj": (jax.random.normal(k4, (d_inner, d_model)) * (d_inner ** -0.5)).astype(dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv, x (B, S, C), w (W, C)."""
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(width):
        out = out + pad[:, i : i + x.shape[1], :] * w[i]
    return jax.nn.silu(out + b)


def _split_proj(p: dict, u: jax.Array, d_model: int, s: SSMConfig):
    d_inner, n_heads, conv_dim = _dims(d_model, s)
    zxbcdt = u @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)
    return z, xbc, dt, d_inner, n_heads


def ssd_reference(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                  C: jax.Array, chunk: int) -> Tuple[jax.Array, jax.Array]:
    """SSD chunked scan (oracle).

    x: (b, s, h, p)   dt: (b, s, h)   A: (h,) negative reals
    B, C: (b, s, g, n)  heads h are grouped onto g = n_groups B/C banks.
    Returns (y (b,s,h,p), final_state (b,h,p,n)).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    rep = h // g

    # fold dt into x and into the decay
    dax = (dt[..., None] * x).astype(jnp.float32)            # (b,s,h,p)
    la = (dt * A).astype(jnp.float32)                        # log a_t  (b,s,h)

    # chunk-major scan inputs: one chunk's quadratic term is materialized at
    # a time (peak memory b*q*q*h instead of b*s*q*h).
    xc = jnp.moveaxis(dax.reshape(b, nc, chunk, h, p), 1, 0)        # (nc,b,q,h,p)
    lac = jnp.moveaxis(la.reshape(b, nc, chunk, h), 1, 0)           # (nc,b,q,h)
    Bc = jnp.moveaxis(B.reshape(b, nc, chunk, g, n), 1, 0).astype(jnp.float32)
    Cc = jnp.moveaxis(C.reshape(b, nc, chunk, g, n), 1, 0).astype(jnp.float32)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(state, inp):
        xq, laq, Bq, Cq = inp                                  # one chunk
        Bh = jnp.repeat(Bq, rep, axis=2)                       # (b,q,h,n)
        Ch = jnp.repeat(Cq, rep, axis=2)
        cum = jnp.cumsum(laq, axis=1)                          # (b,q,h)
        diff = cum[:, :, None, :] - cum[:, None, :, :]         # (b,i,j,h)
        # mask before exp: above the diagonal diff > 0 grows with the
        # chunk, and an overflowed exp there turns the gradient into NaN
        # (inf * 0) even though the forward masks it away
        L = jnp.exp(jnp.where(tri[None, :, :, None], diff, -jnp.inf))
        scores = jnp.einsum("bihn,bjhn->bijh", Ch, Bh) * L
        y = jnp.einsum("bijh,bjhp->bihp", scores, xq)
        # inter-chunk: y_i += exp(cum_i) C_i . S_prev
        y = y + jnp.einsum("bihn,bhpn,bih->bihp", Ch, state, jnp.exp(cum))
        # state update
        decay_to_end = jnp.exp(cum[:, -1:, :] - cum)           # (b,q,h)
        new_state = state * jnp.exp(cum[:, -1])[..., None, None] + jnp.einsum(
            "bjhn,bjh,bjhp->bhpn", Bh, decay_to_end, xq)
        return new_state, y

    init = jnp.zeros((b, h, p, n), jnp.float32)
    final, ys = jax.lax.scan(step, init, (xc, lac, Bc, Cc))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, h, p)
    return y, final


def ssm_mixer(p: dict, u: jax.Array, cfg: ModelConfig, *, use_kernel: bool = False
              ) -> jax.Array:
    """Full Mamba2 mixer: u (B, S, D) -> (B, S, D)."""
    s_cfg = cfg.ssm
    z, xbc, dt, d_inner, n_heads = _split_proj(p, u, cfg.d_model, s_cfg)
    xbc = _causal_conv(xbc, p["conv_w"], p["conv_b"])
    x, B, C = jnp.split(
        xbc, [d_inner, d_inner + s_cfg.n_groups * s_cfg.state_dim], axis=-1
    )
    b, s, _ = u.shape
    x = x.reshape(b, s, n_heads, s_cfg.head_dim)
    B = B.reshape(b, s, s_cfg.n_groups, s_cfg.state_dim)
    C = C.reshape(b, s, s_cfg.n_groups, s_cfg.state_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (b,s,h)
    A = -jnp.exp(p["A_log"])
    if use_kernel:
        from repro.kernels import ops as kops
        y, _ = kops.ssd_scan(x, dt, A, B, C, chunk=s_cfg.chunk_size)
    else:
        y, _ = ssd_reference(x, dt, A, B, C, chunk=min(s_cfg.chunk_size, s))
    y = y + (p["D"][:, None] * x.astype(jnp.float32))
    y = y.reshape(b, s, d_inner).astype(u.dtype)
    y = layers.rms_norm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    return y @ p["out_proj"]


# ---------------------------------------------------------------------------
# decode (recurrent form)
# ---------------------------------------------------------------------------

class SSMState(NamedTuple):
    conv: jax.Array    # (B, W-1, conv_dim) rolling conv window
    ssd: jax.Array     # (B, H, P, N) recurrent state


def init_ssm_state(batch: int, d_model: int, s: SSMConfig, dtype) -> SSMState:
    d_inner, n_heads, conv_dim = _dims(d_model, s)
    return SSMState(
        conv=jnp.zeros((batch, s.conv_width - 1, conv_dim), dtype),
        ssd=jnp.zeros((batch, n_heads, s.head_dim, s.state_dim), jnp.float32),
    )


def ssm_decode_step(p: dict, u: jax.Array, state: SSMState, cfg: ModelConfig
                    ) -> Tuple[jax.Array, SSMState]:
    """One-token recurrent step: u (B, 1, D)."""
    s_cfg = cfg.ssm
    z, xbc, dt, d_inner, n_heads = _split_proj(p, u, cfg.d_model, s_cfg)
    window = jnp.concatenate([state.conv, xbc], axis=1)       # (B, W, conv)
    conv_out = jnp.sum(window * p["conv_w"], axis=1, keepdims=True) + p["conv_b"]
    xbc = jax.nn.silu(conv_out)                               # (B, 1, conv)
    new_conv = window[:, 1:, :]

    x, B, C = jnp.split(
        xbc, [d_inner, d_inner + s_cfg.n_groups * s_cfg.state_dim], axis=-1
    )
    b = u.shape[0]
    x = x.reshape(b, n_heads, s_cfg.head_dim)
    B = B.reshape(b, s_cfg.n_groups, s_cfg.state_dim)
    C = C.reshape(b, s_cfg.n_groups, s_cfg.state_dim)
    rep = n_heads // s_cfg.n_groups
    Bh = jnp.repeat(B, rep, axis=1).astype(jnp.float32)        # (b,h,n)
    Ch = jnp.repeat(C, rep, axis=1).astype(jnp.float32)
    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (b,h)
    a = jnp.exp(dt * -jnp.exp(p["A_log"]))                     # (b,h)
    dax = dt[..., None] * x.astype(jnp.float32)                # (b,h,p)
    new_ssd = state.ssd * a[..., None, None] + dax[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", new_ssd, Ch)
    y = y + p["D"][:, None] * x.astype(jnp.float32)
    y = y.reshape(b, 1, d_inner).astype(u.dtype)
    y = layers.rms_norm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    return y @ p["out_proj"], SSMState(conv=new_conv, ssd=new_ssd)
