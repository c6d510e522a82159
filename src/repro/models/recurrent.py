"""Recurrent blocks: Mamba-2 (SSD), RG-LRU (Griffin/RecurrentGemma) and the
gated short convolution (LFM2).

Both expose a full-sequence ``apply_*`` (training/prefill) and a single-token
``*_step`` (decode) driven by explicit state pytrees — O(1) decode memory,
which is what makes the long_500k cells runnable for these families.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import _dense_init


# ======================================================================
# Mamba-2 block
# ======================================================================

def mamba_dims(cfg: ModelConfig):
    ssm = cfg.ssm
    d_inner = ssm.expand * cfg.d_model
    heads = d_inner // ssm.head_dim
    return d_inner, heads, ssm.d_state, ssm.head_dim


def init_mamba_block(cfg: ModelConfig, key, dtype) -> Dict:
    d = cfg.d_model
    d_inner, h, n, p_ = mamba_dims(cfg)
    conv_dim = d_inner + 2 * n
    ks = jax.random.split(key, 8)
    return {
        "wx": _dense_init(ks[0], (d, d_inner), dtype),
        "wz": _dense_init(ks[1], (d, d_inner), dtype),
        "wb": _dense_init(ks[2], (d, n), dtype),
        "wc": _dense_init(ks[3], (d, n), dtype),
        "wdt": _dense_init(ks[4], (d, h), dtype),
        "dt_bias": jnp.zeros((h,), dtype),
        "a_log": jnp.zeros((h,), jnp.float32),            # a = -exp(a_log)
        "conv_w": _dense_init(ks[5], (cfg.conv_width, conv_dim), dtype,
                              scale=0.5),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), dtype),
        "wo": _dense_init(ks[6], (d_inner, d), dtype),
    }


def _causal_conv(u: jnp.ndarray, w: jnp.ndarray, b, width: int) -> jnp.ndarray:
    """Depthwise causal conv of ``width`` taps. u: [B, S, C]; w: [width, C];
    b: [C] or None."""
    pad = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + u.shape[1]] * w[i] for i in range(width))
    return out if b is None else out + b


def _gated_rmsnorm(y: jnp.ndarray, z: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    y32 = (y * jax.nn.silu(z)).astype(jnp.float32)
    var = jnp.mean(jnp.square(y32), axis=-1, keepdims=True)
    return (y32 * jax.lax.rsqrt(var + 1e-6) * scale.astype(jnp.float32)).astype(y.dtype)


def apply_mamba_block(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
                      use_pallas: bool = False, return_state: bool = False):
    """Full-sequence forward. x: [B, S, D]. With ``return_state`` also returns
    the serving state {conv (pre-conv tail), ssm (final SSD state)}."""
    bsz, s, d = x.shape
    d_inner, h, n, hd = mamba_dims(cfg)
    xp = x @ p["wx"]
    z = x @ p["wz"]
    bc = jnp.concatenate([x @ p["wb"], x @ p["wc"]], axis=-1)
    u_raw = jnp.concatenate([xp, bc], axis=-1)
    u = jax.nn.silu(_causal_conv(u_raw, p["conv_w"], p["conv_b"],
                                 cfg.conv_width))
    xp, b_in, c_in = jnp.split(u, [d_inner, d_inner + n], axis=-1)
    dt = jax.nn.softplus((x @ p["wdt"]).astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))   # [B, S, H]
    a = -jnp.exp(p["a_log"])                                    # [H]
    xh = xp.reshape(bsz, s, h, hd)
    from repro.kernels.ops import ssd
    y, final_state = ssd(xh, dt, a, b_in, c_in, chunk=cfg.ssm.chunk,
                         use_pallas=use_pallas)
    y = y + xh.astype(jnp.float32) * p["d_skip"][None, None, :, None]
    y = y.reshape(bsz, s, d_inner).astype(x.dtype)
    y = _gated_rmsnorm(y, z, p["norm_scale"])
    out = y @ p["wo"]
    if return_state:
        state = {"conv": u_raw[:, -(cfg.conv_width - 1):].astype(jnp.float32),
                 "ssm": final_state}
        return out, state
    return out


def mamba_state_init(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> Dict:
    d_inner, h, n, hd = mamba_dims(cfg)
    conv_dim = d_inner + 2 * n
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, h, hd, n), jnp.float32),
    }


def mamba_block_step(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
                     state: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode. x: [B, 1, D]."""
    bsz = x.shape[0]
    d_inner, h, n, hd = mamba_dims(cfg)
    xt = x[:, 0]
    xp = xt @ p["wx"]
    z = xt @ p["wz"]
    bc = jnp.concatenate([xt @ p["wb"], xt @ p["wc"]], axis=-1)
    u = jnp.concatenate([xp, bc], axis=-1)                    # [B, conv_dim]
    window = jnp.concatenate([state["conv"], u[:, None]], axis=1)  # [B, K, C]
    conv_out = jnp.einsum("bkc,kc->bc", window, p["conv_w"]) + p["conv_b"]
    u = jax.nn.silu(conv_out)
    xp, b_in, c_in = jnp.split(u, [d_inner, d_inner + n], axis=-1)
    dt = jax.nn.softplus((xt @ p["wdt"]).astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))   # [B, H]
    a = -jnp.exp(p["a_log"])
    decay = jnp.exp(dt * a[None, :])                           # [B, H]
    xh = xp.reshape(bsz, h, hd).astype(jnp.float32)
    dbx = jnp.einsum("bhp,bn,bh->bhpn", xh, b_in.astype(jnp.float32), dt)
    ssm = state["ssm"] * decay[..., None, None] + dbx
    y = jnp.einsum("bhpn,bn->bhp", ssm, c_in.astype(jnp.float32))
    y = y + xh * p["d_skip"][None, :, None]
    y = y.reshape(bsz, d_inner).astype(x.dtype)
    y = _gated_rmsnorm(y, z, p["norm_scale"])
    out = (y @ p["wo"])[:, None]
    return out, {"conv": window[:, 1:], "ssm": ssm}


# ======================================================================
# RG-LRU block (Griffin recurrent block)
# ======================================================================

RGLRU_C = 8.0


def init_rglru_block(cfg: ModelConfig, key, dtype) -> Dict:
    d = cfg.d_model
    d_rnn = d  # lru width = d_model (recurrentgemma-2b: 2560)
    ks = jax.random.split(key, 6)
    return {
        "wx": _dense_init(ks[0], (d, d_rnn), dtype),
        "wgate": _dense_init(ks[1], (d, d_rnn), dtype),
        "conv_w": _dense_init(ks[2], (cfg.conv_width, d_rnn), dtype,
                              scale=0.5),
        "conv_b": jnp.zeros((d_rnn,), dtype),
        "w_r": _dense_init(ks[3], (d_rnn, d_rnn), dtype),
        "b_r": jnp.zeros((d_rnn,), jnp.float32),
        "w_i": _dense_init(ks[4], (d_rnn, d_rnn), dtype),
        "b_i": jnp.zeros((d_rnn,), jnp.float32),
        "lam": jnp.full((d_rnn,), 1.0, jnp.float32),          # Λ
        "wo": _dense_init(ks[5], (d_rnn, d), dtype),
    }


def _rglru_gates(p: Dict, u: jnp.ndarray):
    r = jax.nn.sigmoid((u @ p["w_r"]).astype(jnp.float32) + p["b_r"])
    i = jax.nn.sigmoid((u @ p["w_i"]).astype(jnp.float32) + p["b_i"])
    log_a = -RGLRU_C * jax.nn.softplus(p["lam"]) * r          # [..., d_rnn]
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-9))
    gated_in = beta * i * u.astype(jnp.float32)
    return a, gated_in


def rglru_scan(a: jnp.ndarray, gin: jnp.ndarray, h0=None,
               chunk: int = 256) -> jnp.ndarray:
    """h_t = a_t h_{t-1} + gin_t, chunked: associative scan within chunks
    (parallel-friendly) + lax.scan across chunk boundaries. Backward memory
    is O(S/chunk) carried states + one chunk's scan levels, instead of the
    O(S log S) level pyramid of a full-length associative scan."""
    b, s, d = a.shape
    q = min(chunk, s)
    if s % q:
        return _rglru_assoc(a, gin, h0)
    nc = s // q
    ac = a.reshape(b, nc, q, d)
    gc = gin.reshape(b, nc, q, d)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    def chunk_step(h, inp):
        a_q, g_q = inp                                    # [B, Q, D]
        a_sc, h_in = jax.lax.associative_scan(combine, (a_q, g_q), axis=1)
        h_seq = h_in + a_sc * h[:, None, :]               # carry-in correction
        return h_seq[:, -1], h_seq

    h0 = h0 if h0 is not None else jnp.zeros((b, d), a.dtype)
    _, hs = jax.lax.scan(chunk_step, h0,
                         (jnp.moveaxis(ac, 1, 0), jnp.moveaxis(gc, 1, 0)))
    return jnp.moveaxis(hs, 0, 1).reshape(b, s, d)


def _rglru_assoc(a, gin, h0=None):
    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2
    a_sc, h = jax.lax.associative_scan(combine, (a, gin), axis=1)
    if h0 is not None:
        h = h + a_sc * h0[:, None, :]
    return h


def apply_rglru_block(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
                      use_pallas: bool = False) -> jnp.ndarray:
    """Full-sequence forward via chunked linear recurrence. x: [B, S, D]."""
    gate = jax.nn.gelu(x @ p["wgate"])
    u = _causal_conv(x @ p["wx"], p["conv_w"], p["conv_b"], cfg.conv_width)
    a, gin = _rglru_gates(p, u)                               # [B, S, d_rnn]
    h = rglru_scan(a, gin)
    y = (h.astype(x.dtype) * gate)
    return y @ p["wo"]


def rglru_state_init(cfg: ModelConfig, batch: int) -> Dict:
    d_rnn = cfg.d_model
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, d_rnn), jnp.float32),
        "h": jnp.zeros((batch, d_rnn), jnp.float32),
    }


def rglru_block_step(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
                     state: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode. x: [B, 1, D]."""
    xt = x[:, 0]
    gate = jax.nn.gelu(xt @ p["wgate"])
    u_raw = xt @ p["wx"]
    window = jnp.concatenate([state["conv"], u_raw[:, None].astype(jnp.float32)],
                             axis=1)
    u = jnp.einsum("bkc,kc->bc", window.astype(x.dtype), p["conv_w"]) + p["conv_b"]
    a, gin = _rglru_gates(p, u)
    h = a * state["h"] + gin
    y = (h.astype(x.dtype) * gate) @ p["wo"]
    return y[:, None], {"conv": window[:, 1:], "h": h}


# ======================================================================
# Gated short convolution (LFM2)
# ======================================================================

def init_short_conv(cfg: ModelConfig, key, dtype) -> Dict:
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    return {
        "w_in": _dense_init(ks[0], (d, 3 * d), dtype),     # -> B, C, x
        "conv_w": _dense_init(ks[1], (cfg.conv_width, d), dtype, scale=0.5),
        "w_out": _dense_init(ks[2], (d, d), dtype),
    }


def _short_conv_in(p: Dict, x: jnp.ndarray):
    """(C, B*x in float32): the output gate, and what the conv runs over."""
    b, c, xs = jnp.split(x @ p["w_in"], 3, axis=-1)
    return c, (b * xs).astype(jnp.float32)


def apply_short_conv(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
                     return_state: bool = False):
    """Full-sequence forward: out = W_out(C * conv(B * x)), the conv causal
    and depthwise over time, in float32. x: [B, S, D]. With
    ``return_state`` also the decode state: the conv's last ``width - 1``
    inputs, zeros before the sequence's start."""
    c, v = _short_conv_in(p, x)
    conv = _causal_conv(v, p["conv_w"].astype(jnp.float32), None,
                        cfg.conv_width)
    out = (c.astype(jnp.float32) * conv).astype(x.dtype) @ p["w_out"]
    if return_state:
        tail = jnp.pad(v, ((0, 0), (cfg.conv_width - 1, 0), (0, 0)))
        return out, {"conv": tail[:, v.shape[1]:]}
    return out


def short_conv_state_init(cfg: ModelConfig, batch: int) -> Dict:
    return {"conv": jnp.zeros((batch, cfg.conv_width - 1, cfg.d_model),
                              jnp.float32)}


def short_conv_step(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
                    state: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode. x: [B, 1, D]; state["conv"]: [B, width - 1, D]."""
    c, v = _short_conv_in(p, x[:, 0])
    window = jnp.concatenate([state["conv"], v[:, None]], axis=1)
    w = p["conv_w"].astype(jnp.float32)
    conv = sum(window[:, i] * w[i] for i in range(cfg.conv_width))
    out = (c.astype(jnp.float32) * conv).astype(x.dtype) @ p["w_out"]
    return out[:, None], {"conv": window[:, 1:]}
