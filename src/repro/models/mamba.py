"""Mamba (selective SSM) layer -- the recurrent sublayer of jamba.

Forward-only selective scan via ``lax.scan`` over time (ZO fine-tuning
never backprops through the scan, so no remat policy is needed).
Decode carries (conv_state, ssm_state) explicitly.

The full-sequence apply threads an optional ``PerturbCtx``: every weight
use applies ``coeff*z`` in place (dense projections via ``ctx``-aware
``L.dense``, conv/SSM leaves via transient ``ctx.perturb``), which is
what lets the hybrid family run the fused ZO loss with zero transient
parameter copies.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.perturb_ctx import sub as _sub
from repro.models import layers as L
from repro.optim.quant import deq as _deq


def _leaf(p, name, ctx):
    """p[name] + coeff*z under a ctx; the bare (dequantized) leaf
    without one."""
    return _deq(p[name]) if ctx is None else ctx.perturb(name, p[name])


def _dims(cfg, d_model=None):
    d = d_model or cfg.d_model
    d_inner = cfg.mamba_expand * d
    dt_rank = max(1, d // 16)
    return d, d_inner, dt_rank


def mamba_init(cfg, key, d_model=None):
    d, di, dtr = _dims(cfg, d_model)
    n = cfg.mamba_d_state
    ks = jax.random.split(key, 6)
    dt = L._dt(cfg)
    return {
        "in_proj": L.dense_init(ks[0], d, 2 * di, dt),
        "conv_w": (jax.random.normal(ks[1], (cfg.mamba_d_conv, di),
                                     jnp.float32) * 0.1).astype(dt),
        "conv_b": jnp.zeros((di,), dt),
        "x_proj": L.dense_init(ks[2], di, dtr + 2 * n, dt),
        "dt_proj": L.dense_init(ks[3], dtr, di, dt, bias=True),
        # A initialized to -[1..n] per channel (S4D-real init)
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, n + 1, dtype=jnp.float32), (di, n))),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": L.dense_init(ks[4], di, d, dt,
                                 scale=0.02 / max(cfg.n_layers, 1) ** 0.5),
    }


def _ssm_inputs(cfg, p, xc, d_model=None, ctx=None):
    """xc: (B, S, di) post-conv. Returns dt, Bmat, Cmat (f32)."""
    _, _, dtr = _dims(cfg, d_model)
    n = cfg.mamba_d_state
    proj = L.dense(p["x_proj"], xc, _sub(ctx, "x_proj")).astype(jnp.float32)
    dt_raw, bmat, cmat = jnp.split(proj, [dtr, dtr + n], axis=-1)
    dtp = _sub(ctx, "dt_proj")
    dt = jax.nn.softplus(dt_raw @ _leaf(p["dt_proj"], "w",
                                        dtp).astype(jnp.float32)
                         + _leaf(p["dt_proj"], "b", dtp).astype(jnp.float32))
    return dt, bmat, cmat


def _scan_ssm(p, xc, dt, bmat, cmat, h0=None, ctx=None):
    """Selective scan. xc: (B,S,di); dt: (B,S,di); b/c: (B,S,n)."""
    a = -jnp.exp(_leaf(p, "A_log", ctx))           # (di, n)
    bsz, _, di = xc.shape
    n = a.shape[-1]
    h0 = jnp.zeros((bsz, di, n), jnp.float32) if h0 is None else h0

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp                  # (B,di),(B,di),(B,n),(B,n)
        da = jnp.exp(dt_t[..., None] * a)          # (B, di, n)
        dbx = (dt_t * x_t.astype(jnp.float32))[..., None] * b_t[:, None, :]
        h = da * h + dbx
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    xs = (xc.transpose(1, 0, 2), dt.transpose(1, 0, 2),
          bmat.transpose(1, 0, 2), cmat.transpose(1, 0, 2))
    h, ys = jax.lax.scan(step, h0, xs)
    y = ys.transpose(1, 0, 2) + xc.astype(jnp.float32) * _leaf(p, "D", ctx)
    return y.astype(xc.dtype), h


def _causal_conv(p, x, d_conv, ctx=None):
    """Depthwise causal conv over time. x: (B, S, di)."""
    pad = jnp.pad(x, ((0, 0), (d_conv - 1, 0), (0, 0)))
    conv_w = _leaf(p, "conv_w", ctx)
    out = sum(pad[:, i:i + x.shape[1], :] * conv_w[i]
              for i in range(d_conv))
    return out + _leaf(p, "conv_b", ctx)


def mamba_apply(cfg, p, x, d_model=None, ctx=None):
    """Full-sequence forward. x: (B, S, D) -> (B, S, D)."""
    xz = L.dense(p["in_proj"], x, _sub(ctx, "in_proj"))
    xi, z = jnp.split(xz, 2, axis=-1)
    xc = jax.nn.silu(_causal_conv(p, xi, cfg.mamba_d_conv, ctx))
    dt, bmat, cmat = _ssm_inputs(cfg, p, xc, d_model, ctx)
    y, _ = _scan_ssm(p, xc, dt, bmat, cmat, ctx=ctx)
    return L.dense(p["out_proj"], y * jax.nn.silu(z), _sub(ctx, "out_proj"))


def mamba_init_state(cfg, bsz, d_model, dtype):
    di = cfg.mamba_expand * d_model
    return {
        "conv": jnp.zeros((bsz, cfg.mamba_d_conv - 1, di), dtype),
        "ssm": jnp.zeros((bsz, di, cfg.mamba_d_state), jnp.float32),
    }


def mamba_prefill(cfg, p, state, x, d_model=None):
    """Multi-token continuation: full-sequence mamba from an explicit
    (conv, ssm) state, returning the state after the last token.
    ``mamba_step`` is the S=1 special case; with a zero state this equals
    ``mamba_apply`` (whose implicit conv padding is exactly the zero
    conv window)."""
    d_conv = cfg.mamba_d_conv
    s = x.shape[1]
    xz = L.dense(p["in_proj"], x)
    xi, z = jnp.split(xz, 2, axis=-1)                 # (B, S, di)
    window = jnp.concatenate([state["conv"], xi], axis=1)
    conv_w = _leaf(p, "conv_w", None)
    xc = sum(window[:, i:i + s, :] * conv_w[i]
             for i in range(d_conv)) + _leaf(p, "conv_b", None)
    xc = jax.nn.silu(xc)
    dt, bmat, cmat = _ssm_inputs(cfg, p, xc, d_model)
    y, h = _scan_ssm(p, xc, dt, bmat, cmat, h0=state["ssm"])
    out = L.dense(p["out_proj"], y * jax.nn.silu(z))
    return out, {"conv": window[:, s:, :], "ssm": h}


def mamba_step(cfg, p, state, x, d_model=None):
    """Single decode step. x: (B, 1, D) -> (B, 1, D), updated state."""
    xz = L.dense(p["in_proj"], x)
    xi, z = jnp.split(xz, 2, axis=-1)                 # (B, 1, di)
    window = jnp.concatenate([state["conv"], xi], axis=1)
    conv_w = _leaf(p, "conv_w", None)
    xc = sum(window[:, i:i + 1, :] * conv_w[i]
             for i in range(cfg.mamba_d_conv)) + _leaf(p, "conv_b", None)
    xc = jax.nn.silu(xc)
    dt, bmat, cmat = _ssm_inputs(cfg, p, xc, d_model)
    y, h = _scan_ssm(p, xc, dt, bmat, cmat, h0=state["ssm"])
    out = L.dense(p["out_proj"], y * jax.nn.silu(z))
    return out, {"conv": window[:, 1:, :], "ssm": h}
