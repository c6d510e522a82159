"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth the verification cascade (and every kernel test)
compares against. No tiling, no scheduling — just the math.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.kernels.epilogue import EpilogueOp, apply_epilogue


# ----------------------------------------------------------------------
def matmul_fused_ref(a: jnp.ndarray, b: jnp.ndarray,
                     epilogue: Optional[List[EpilogueOp]] = None,
                     operands: Optional[Dict[str, jnp.ndarray]] = None,
                     transpose_b: bool = False,
                     reduction: Optional[str] = None) -> jnp.ndarray:
    if transpose_b:
        b = b.T
    y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    y = apply_epilogue(y, epilogue or [], operands or {})
    if reduction == "sum":
        y = jnp.sum(y, axis=-1)
    elif reduction == "max":
        y = jnp.max(y, axis=-1)
    elif reduction == "min":
        y = jnp.min(y, axis=-1)
    elif reduction == "mean":
        y = jnp.mean(y, axis=-1)
    return y


# ----------------------------------------------------------------------
def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = False, scale: Optional[float] = None,
                  window: Optional[int] = None) -> jnp.ndarray:
    """Full-softmax attention oracle. q,k,v: [B, H, S, D] (H may be grouped
    outside). Computes in f32."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    sq, skv = q.shape[-2], k.shape[-2]
    if causal or window is not None:
        qi = jnp.arange(sq)[:, None] + (skv - sq)  # align ends (decode-friendly)
        ki = jnp.arange(skv)[None, :]
        mask = jnp.ones((sq, skv), bool)
        if causal:
            mask &= ki <= qi
        if window is not None:
            mask &= ki > qi - window
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def decode_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         lengths: Optional[jnp.ndarray] = None,
                         scale: Optional[float] = None) -> jnp.ndarray:
    """Single-token decode oracle. q: [B, H, D]; k,v: [B, H, S, D];
    lengths: [B] valid KV lengths (None = all valid)."""
    q32 = q.astype(jnp.float32)
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    s = jnp.einsum("bhd,bhsd->bhs", q32, k32) * scale
    if lengths is not None:
        mask = jnp.arange(k.shape[-2])[None, None, :] < lengths[:, None, None]
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, v32)


# ----------------------------------------------------------------------
def moe_experts_ref(x: jnp.ndarray, counts: jnp.ndarray, wi: jnp.ndarray,
                    wg: jnp.ndarray, wo: jnp.ndarray) -> jnp.ndarray:
    """Grouped SwiGLU oracle. x: [E, C, D] grouped by expert, counts: [E];
    rows at or past an expert's count give zero."""
    h = jnp.einsum("ecd,edf->ecf", x, wi, preferred_element_type=jnp.float32)
    g = jnp.einsum("ecd,edf->ecf", x, wg, preferred_element_type=jnp.float32)
    a = (jax.nn.silu(h) * g).astype(x.dtype)
    y = jnp.einsum("ecf,efd->ecd", a, wo, preferred_element_type=jnp.float32)
    live = jnp.arange(x.shape[1])[None, :, None] < counts[:, None, None]
    return jnp.where(live, y, 0.0).astype(x.dtype)


# ----------------------------------------------------------------------
def rmsnorm_ref(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------------
def elementwise_chain_ref(x: jnp.ndarray, epilogue: List[EpilogueOp],
                          operands: Optional[Dict[str, jnp.ndarray]] = None
                          ) -> jnp.ndarray:
    return apply_epilogue(x.astype(jnp.float32), epilogue, operands or {}).astype(x.dtype)


# ----------------------------------------------------------------------
def ssd_ref(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
            b: jnp.ndarray, c: jnp.ndarray,
            initial_state: Optional[jnp.ndarray] = None):
    """Mamba-2 SSD oracle (sequential scan, exact).

    x:  [B, L, H, P]   token inputs per head
    dt: [B, L, H]      softplus-ed step sizes (>0)
    a:  [H]            negative state decay rate per head
    b:  [B, L, N]      input projection (shared across heads, G=1)
    c:  [B, L, N]      output projection
    returns y: [B, L, H, P], final_state: [B, H, P, N]
    """
    B, L, H, P = x.shape
    N = b.shape[-1]
    x32, dt32 = x.astype(jnp.float32), dt.astype(jnp.float32)
    b32, c32 = b.astype(jnp.float32), c.astype(jnp.float32)
    a32 = a.astype(jnp.float32)

    def step(state, inp):
        xt, dtt, bt, ct = inp          # [B,H,P], [B,H], [B,N], [B,N]
        decay = jnp.exp(dtt * a32[None, :])            # [B,H]
        dbx = jnp.einsum("bhp,bn,bh->bhpn", xt, bt, dtt)
        state = state * decay[..., None, None] + dbx   # [B,H,P,N]
        yt = jnp.einsum("bhpn,bn->bhp", state, ct)
        return state, yt

    state0 = (initial_state.astype(jnp.float32) if initial_state is not None
              else jnp.zeros((B, H, P, N), jnp.float32))
    xs = (jnp.moveaxis(x32, 1, 0), jnp.moveaxis(dt32, 1, 0),
          jnp.moveaxis(b32, 1, 0), jnp.moveaxis(c32, 1, 0))
    final, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), final


def ssd_chunked_ref(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                    b: jnp.ndarray, c: jnp.ndarray, chunk: int = 128,
                    initial_state: Optional[jnp.ndarray] = None):
    """Chunked SSD in pure jnp — the same intra/inter-chunk decomposition as
    the Pallas kernel, vectorized over chunks. Training-friendly: backward
    saves O(L/chunk) states instead of O(L) (the sequential ``ssd_ref``
    backward is O(L) and explodes at 4k+ sequence lengths)."""
    B, L, H, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q
    x32 = x.astype(jnp.float32).reshape(B, nc, Q, H, P)
    dt32 = dt.astype(jnp.float32).reshape(B, nc, Q, H)
    b32 = b.astype(jnp.float32).reshape(B, nc, Q, N)
    c32 = c.astype(jnp.float32).reshape(B, nc, Q, N)
    a32 = a.astype(jnp.float32)

    aq = dt32 * a32[None, None, None, :]                 # [B, nc, Q, H]
    cums = jnp.cumsum(aq, axis=2)

    # intra-chunk: masked decay-weighted attention (per chunk, batched)
    scores = jnp.einsum("bcqn,bcsn->bcqs", c32, b32)      # [B, nc, Q, Q]
    # li[b,c,q,s,h] = cums[q] - cums[s]
    li = cums[:, :, :, None, :] - cums[:, :, None, :, :]  # [B, nc, Q, S, H]
    mask = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])
    decay = jnp.where(mask[None, None, :, :, None], jnp.exp(li), 0.0)
    w = scores[..., None] * decay * dt32[:, :, None, :, :]
    y_intra = jnp.einsum("bcqsh,bcshp->bcqhp", w, x32)

    # chunk-boundary states: S_c' = exp(total) S_c + ds_c
    total = cums[:, :, -1, :]                             # [B, nc, H]
    wgt = jnp.exp(total[:, :, None, :] - cums) * dt32     # [B, nc, Q, H]
    ds = jnp.einsum("bcqhp,bcqn,bcqh->bchpn", x32, b32, wgt)

    def chunk_step(state, inp):
        tot, ds_c = inp                                    # [B,H], [B,H,P,N]
        out = state                                        # state entering chunk
        new = state * jnp.exp(tot)[..., None, None] + ds_c
        return new, out

    state0 = (initial_state.astype(jnp.float32) if initial_state is not None
              else jnp.zeros((B, H, P, N), jnp.float32))
    final, entry_states = jax.lax.scan(
        chunk_step, state0,
        (jnp.moveaxis(total, 1, 0), jnp.moveaxis(ds, 1, 0)))
    entry = jnp.moveaxis(entry_states, 0, 1)               # [B, nc, H, P, N]

    y_inter = jnp.einsum("bcqn,bchpn->bcqhp", c32, entry) \
        * jnp.exp(cums)[..., None]
    y = (y_intra + y_inter).reshape(B, L, H, P)
    return y.astype(x.dtype), final
