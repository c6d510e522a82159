"""jit'd public wrappers for the Pallas kernels + the tuned-config registry.

Models call these entry points; each consults :class:`TunedRegistry` — the
output artifact of the Forge pipeline (§DESIGN 3.1) — for the kernel config
matching the call-site signature, falling back to the hardware query system's
shape-aware defaults. ``use_pallas=False`` routes to the jnp oracle (the path
the multi-pod dry-run lowers, since this container compiles for CPU).
"""

from __future__ import annotations

import functools
import json
import os
import pathlib
from typing import Dict, List, Optional

import jax.numpy as jnp

from repro.hw.query import HardwareQuery
from repro.hw.specs import TPU_V5E, spec_for_device
from repro.kernels import ref as ref_ops
from repro.kernels.epilogue import EpilogueOp
from repro.kernels.matmul_fused import matmul_fused
from repro.kernels.flash_attention import flash_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.moe_experts import moe_experts
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.elementwise import elementwise_chain
from repro.kernels.platform import resolve_interpret
from repro.kernels.ssd_scan import ssd_scan


@functools.lru_cache(maxsize=None)
def _query() -> HardwareQuery:
    """Tile defaults for the chip the kernels compile for, resolved from its
    ``device_kind``; the interpreter stands in for the v5e target."""
    return HardwareQuery(TPU_V5E if resolve_interpret() else spec_for_device())


class TunedRegistry:
    """Persisted kernel configs keyed by (kernel, signature)."""

    def __init__(self, path: Optional[pathlib.Path] = None):
        default = pathlib.Path(__file__).resolve().parents[1] / "configs" / "tuned" / "kernels.json"
        self.path = pathlib.Path(
            path or os.environ.get("REPRO_TUNED_KERNELS", default))
        self._cache: Optional[Dict] = None

    def _load(self) -> Dict:
        if self._cache is None:
            if self.path.exists():
                self._cache = json.loads(self.path.read_text())
            else:
                self._cache = {}
        return self._cache

    def get(self, kernel: str, signature: str) -> Optional[Dict]:
        return self._load().get(kernel, {}).get(signature)

    def put(self, kernel: str, signature: str, config: Dict):
        data = self._load()
        data.setdefault(kernel, {})[signature] = config
        self.path.parent.mkdir(parents=True, exist_ok=True)
        tmp = self.path.with_suffix(".tmp")
        tmp.write_text(json.dumps(data, indent=2, sort_keys=True))
        tmp.replace(self.path)


REGISTRY = TunedRegistry()


def _sig(*parts) -> str:
    return "/".join(str(p) for p in parts)


# ----------------------------------------------------------------------
def fused_matmul(a, b, epilogue: Optional[List[EpilogueOp]] = None,
                 operands=None, reduction=None, *, use_pallas: bool = True,
                 config: Optional[Dict] = None):
    if not use_pallas:
        return ref_ops.matmul_fused_ref(a, b, epilogue, operands,
                                        reduction=reduction)
    m, k = a.shape
    n = b.shape[1]
    cfg = config or REGISTRY.get("matmul_fused", _sig(m, n, k, a.dtype)) or {}
    if not cfg:
        p = _query().get_optimal_params(m, n, k, str(a.dtype))
        cfg = {"block_m": p.block_m, "block_n": p.block_n, "block_k": p.block_k,
               "group_m": p.group_m, "num_stages": p.num_stages}
    return matmul_fused(a, b,
                        block_m=min(cfg.get("block_m", 128), m),
                        block_n=min(cfg.get("block_n", 128), n),
                        block_k=min(cfg.get("block_k", 128), k),
                        group_m=cfg.get("group_m", 1),
                        num_stages=cfg.get("num_stages", 2),
                        epilogue=epilogue, operands=operands,
                        reduction=reduction)


def attention(q, k, v, *, causal=False, window=None,
              use_pallas: bool = True, config: Optional[Dict] = None):
    if not use_pallas:
        return ref_ops.attention_ref(q, k, v, causal=causal, window=window)
    sq, skv, d = q.shape[-2], k.shape[-2], q.shape[-1]
    cfg = config or REGISTRY.get("flash_attention", _sig(sq, skv, d, q.dtype)) or {}
    if not cfg:
        p = _query().get_attention_params(sq, skv, d, str(q.dtype))
        cfg = {"block_q": p.block_m, "block_kv": p.block_n}
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=cfg.get("block_q", 128),
                           block_kv=cfg.get("block_kv", 128))


def decode_attn(q, k, v, *, lengths=None, use_pallas: bool = True,
                config: Optional[Dict] = None):
    if not use_pallas:
        return ref_ops.decode_attention_ref(q, k, v, lengths=lengths)
    s = k.shape[-2]
    cfg = config or REGISTRY.get("decode_attention", _sig(s, q.shape[-1], q.dtype)) or {}
    return decode_attention(q, k, v, lengths=lengths,
                            block_kv=cfg.get("block_kv", min(512, s)))


def experts(x, counts, wi, wg, wo, *, use_pallas: bool = True,
            config: Optional[Dict] = None):
    """Grouped SwiGLU over the held experts; x: [E, C, D] grouped by expert,
    counts: [E] rows each."""
    if not use_pallas:
        return ref_ops.moe_experts_ref(x, counts, wi, wg, wo)
    cfg = config or REGISTRY.get(
        "moe_experts", _sig(x.shape[1], x.shape[2], wi.shape[-1], x.dtype)) or {}
    return moe_experts(x, counts, wi, wg, wo,
                       block_f=cfg.get("block_f", 256),
                       block_rows=cfg.get("block_rows", 64))


def rms_norm(x, w, *, eps=1e-6, use_pallas: bool = True):
    if not use_pallas:
        return ref_ops.rmsnorm_ref(x, w, eps=eps)
    lead = x.shape[:-1]
    d = x.shape[-1]
    flat = x.reshape(-1, d)
    out = rmsnorm(flat, w, eps=eps)
    return out.reshape(*lead, d)


def fused_elementwise(x, epilogue: List[EpilogueOp], operands=None, *,
                      use_pallas: bool = True):
    if not use_pallas:
        return ref_ops.elementwise_chain_ref(x, epilogue, operands)
    lead = x.shape[:-1]
    c = x.shape[-1]
    flat = x.reshape(-1, c)
    out = elementwise_chain(flat, epilogue, operands=operands)
    return out.reshape(*lead, c)


def ssd(x, dt, a, b, c, *, chunk=128, use_pallas: bool = True):
    """x: [B, L, H, P], dt: [B, L, H], a: [H], b/c: [B, L, N]."""
    if not use_pallas:
        l = x.shape[1]
        if l % min(chunk, l) == 0 and l > 1:
            # chunked jnp path: same decomposition as the Pallas kernel,
            # O(L/chunk) backward state (the sequential ref is O(L))
            return ref_ops.ssd_chunked_ref(x, dt, a, b, c,
                                           chunk=min(chunk, l))
        return ref_ops.ssd_ref(x, dt, a, b, c)
    bsz, l, h, p = x.shape
    n = b.shape[-1]
    xf = jnp.transpose(x, (0, 2, 1, 3)).reshape(bsz * h, l, p)
    dtf = jnp.transpose(dt, (0, 2, 1)).reshape(bsz * h, l)
    af = jnp.broadcast_to(a[None, :], (bsz, h)).reshape(bsz * h, 1)
    bf = jnp.broadcast_to(b[:, None], (bsz, h, l, n)).reshape(bsz * h, l, n)
    cf = jnp.broadcast_to(c[:, None], (bsz, h, l, n)).reshape(bsz * h, l, n)
    y, s = ssd_scan(xf, dtf, af, bf, cf, chunk=min(chunk, l))
    y = y.reshape(bsz, h, l, p).transpose(0, 2, 1, 3)
    s = s.reshape(bsz, h, p, n)
    return y, s
