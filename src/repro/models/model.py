"""Unified model API over the five architecture families.

  init_params(cfg, key, dtype)                      -> params pytree
  forward(cfg, params, batch, flags)                -> (logits, aux_loss)
  init_cache(cfg, batch, max_len, dtype)            -> cache pytree
  prefill(cfg, params, batch, flags)                -> (last_logits, cache)
  decode_step(cfg, params, cache, tokens, pos, flags) -> (logits, cache)

Families: dense / moe (scan-over-layers), ssm (mamba2, scan), hybrid
(recurrentgemma, lfm2: per-layer loop over the block pattern of attention,
RG-LRU and short-conv mixers, each with a dense MLP or held experts), encdec
(whisper, scan per stack). ``batch`` may carry ``prefix_embeds`` (VLM patch
stub) or ``frames`` (audio frame stub) per the assignment's frontend-stub
rule.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import recurrent as R


@dataclasses.dataclass(frozen=True)
class RuntimeFlags:
    use_pallas: bool = False
    chunked_attention: bool = True
    remat: bool = True
    scan_layers: bool = True
    moe_capacity_factor: float = 1.25  # GShard default; tests may raise it
    loss_chunks: int = 8               # streamed-CE chunks (1 = monolithic)


DEFAULT_FLAGS = RuntimeFlags()


# ======================================================================
# init
# ======================================================================

def _init_dense_layer(cfg: ModelConfig, key, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    p = {"ln1": L.init_norm(cfg, dtype),
         "attn": L.init_attention(cfg, ks[0], dtype),
         "ln2": L.init_norm(cfg, dtype)}
    if cfg.moe:
        p["moe"] = L.init_moe(cfg, ks[1], dtype)
    else:
        p["mlp"] = L.init_mlp(cfg, ks[1], dtype)
    return p


def _init_encdec_layer(cfg: ModelConfig, key, dtype, decoder: bool) -> Dict:
    ks = jax.random.split(key, 4)
    p = {"ln1": L.init_norm(cfg, dtype),
         "attn": L.init_attention(cfg, ks[0], dtype),
         "ln2": L.init_norm(cfg, dtype),
         "mlp": L.init_mlp(cfg, ks[1], dtype)}
    if decoder:
        p["ln_x"] = L.init_norm(cfg, dtype)
        p["xattn"] = L.init_attention(cfg, ks[2], dtype, cross=True)
    return p


def _init_hybrid_layer(cfg: ModelConfig, key, dtype, kind: str,
                       index: int) -> Dict:
    ks = jax.random.split(key, 3)
    p = {"ln1": L.init_norm(cfg, dtype), "ln2": L.init_norm(cfg, dtype)}
    if cfg.moe is not None and index >= cfg.num_dense_layers:
        p["moe"] = L.init_moe(cfg, ks[1], dtype)
    else:
        p["mlp"] = L.init_mlp(cfg, ks[1], dtype, d_ff=cfg.dense_d_ff)
    if kind == "attn":
        p["attn"] = L.init_attention(cfg, ks[0], dtype)
    elif kind == "conv":
        p["conv"] = R.init_short_conv(cfg, ks[0], dtype)
    else:
        p["rglru"] = R.init_rglru_block(cfg, ks[0], dtype)
    return p


def _holds_experts(cfg: ModelConfig, i: int) -> bool:
    """Layer i of a hybrid computes a held share of its experts."""
    return (cfg.moe is not None and cfg.moe.held is not None
            and i >= cfg.num_dense_layers)


def hybrid_pattern(cfg: ModelConfig):
    pat = cfg.block_pattern or ("attn",)
    return [pat[i % len(pat)] for i in range(cfg.num_layers)]


def _hybrid_ffn(cfg: ModelConfig, flags: RuntimeFlags, layer, h):
    """The hybrid layer's feed-forward half, with its residual: (h, aux,
    counts), counts being the rows each held expert computed (None unless
    the layer holds a share of its experts)."""
    inner = L.apply_norm(cfg, layer["ln2"], h, flags.use_pallas)
    if "moe" not in layer:
        return (h + L.apply_mlp(cfg, layer["mlp"], inner, flags.use_pallas),
                jnp.zeros((), jnp.float32), None)
    aux = L.moe_aux_loss(cfg, layer["moe"], inner)
    if cfg.moe.held is not None:
        y, counts = L.apply_moe_held(cfg, layer["moe"], inner,
                                     flags.use_pallas)
        return h + y, aux, counts
    return h + L.apply_moe(cfg, layer["moe"], inner, flags.use_pallas,
                           capacity_factor=flags.moe_capacity_factor), aux, None


def _expert_counters(cfg: ModelConfig) -> Dict:
    """A held-expert layer's cache entry counts its experts' work since the
    cache was made: the rows each computed (``expert_rows``) and the calls
    in which it had any (``expert_calls``), so that a caller can tell the
    weights those calls had to read."""
    z = jnp.zeros((cfg.moe.held,), jnp.int32)
    return {"expert_rows": z, "expert_calls": z}


def _count_experts(entry: Dict, counts) -> Dict:
    return {"expert_rows": entry["expert_rows"] + counts,
            "expert_calls": entry["expert_calls"] + (counts > 0)}


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Dict:
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab, cfg.d_model), jnp.float32)
                  * 0.02).astype(dtype),
        "ln_f": L.init_norm(cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L._dense_init(keys[1], (cfg.d_model, cfg.vocab), dtype)

    if cfg.family in ("dense", "moe"):
        lkeys = jax.random.split(keys[2], cfg.num_layers)
        params["layers"] = jax.vmap(
            lambda k: _init_dense_layer(cfg, k, dtype))(lkeys)
    elif cfg.family == "ssm":
        lkeys = jax.random.split(keys[2], cfg.num_layers)
        params["layers"] = jax.vmap(
            lambda k: {"ln1": L.init_norm(cfg, dtype),
                       "mamba": R.init_mamba_block(cfg, k, dtype)})(lkeys)
    elif cfg.family == "hybrid":
        lkeys = jax.random.split(keys[2], cfg.num_layers)
        kinds = hybrid_pattern(cfg)
        params["layers"] = [
            _init_hybrid_layer(cfg, lkeys[i], dtype, kinds[i], i)
            for i in range(cfg.num_layers)]
    elif cfg.family == "encdec":
        ekeys = jax.random.split(keys[3], cfg.encoder_layers)
        dkeys = jax.random.split(keys[4], cfg.num_layers)
        params["enc_layers"] = jax.vmap(
            lambda k: _init_encdec_layer(cfg, k, dtype, decoder=False))(ekeys)
        params["layers"] = jax.vmap(
            lambda k: _init_encdec_layer(cfg, k, dtype, decoder=True))(dkeys)
        params["enc_ln_f"] = L.init_norm(cfg, dtype)
        maxp = min(cfg.max_seq_len, 32768)
        params["pos_embed"] = (jax.random.normal(
            keys[5], (maxp, cfg.d_model), jnp.float32) * 0.01).astype(dtype)
    else:
        raise ValueError(cfg.family)
    return params


# ======================================================================
# embedding / unembedding
# ======================================================================

def _embed(cfg: ModelConfig, params, batch) -> jnp.ndarray:
    from repro.sharding.rules import gather_fsdp
    tokens = batch["tokens"]
    x = jnp.take(gather_fsdp({"embed": params["embed"]})["embed"],
                 tokens, axis=0)
    if cfg.num_prefix_embeds and "prefix_embeds" in batch:
        x = jnp.concatenate([batch["prefix_embeds"].astype(x.dtype), x], axis=1)
    return x


def _unembed(cfg: ModelConfig, params, x) -> jnp.ndarray:
    from repro.sharding.rules import gather_fsdp
    if cfg.tie_embeddings:
        w = gather_fsdp({"embed": params["embed"]})["embed"].T
    else:
        w = gather_fsdp({"unembed": params["unembed"]})["unembed"]
    return (x @ w).astype(jnp.float32)


# ======================================================================
# blocks
# ======================================================================

def _dense_block(cfg: ModelConfig, flags: RuntimeFlags, x, layer,
                 causal=True, window=None, use_rope=True):
    from repro.sharding.rules import gather_fsdp
    layer = gather_fsdp(layer)
    h = L.apply_attention(cfg, layer["attn"],
                          L.apply_norm(cfg, layer["ln1"], x, flags.use_pallas),
                          causal=causal, window=window, use_rope=use_rope,
                          use_pallas=flags.use_pallas,
                          chunked=flags.chunked_attention)
    x = x + h
    inner = L.apply_norm(cfg, layer["ln2"], x, flags.use_pallas)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe:
        y = L.apply_moe(cfg, layer["moe"], inner, flags.use_pallas,
                        capacity_factor=flags.moe_capacity_factor)
        aux = L.moe_aux_loss(cfg, layer["moe"], inner)
    else:
        y = L.apply_mlp(cfg, layer["mlp"], inner, flags.use_pallas)
    # sequence-parallel residual carry: what the layer scan saves for
    # backward is S-sharded over the model axis
    return L.shard_hint(x + y, ("pod", "data"), "model", None), aux


def _scan_blocks(cfg, flags, x, layers_params, block_fn):
    def body(carry, layer):
        h, aux = carry
        h2, a = block_fn(h, layer)
        return (h2, aux + a), None
    if flags.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               layers_params)
    return x, aux


# ======================================================================
# forward (training / scoring)
# ======================================================================

def forward(cfg: ModelConfig, params, batch: Dict, flags: RuntimeFlags =
            DEFAULT_FLAGS) -> Tuple[jnp.ndarray, jnp.ndarray]:
    hidden, aux = forward_hidden(cfg, params, batch, flags)
    return _unembed(cfg, params, hidden), aux


def forward_hidden(cfg: ModelConfig, params, batch: Dict,
                   flags: RuntimeFlags = DEFAULT_FLAGS
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    # backbone up to (and including) the final norm; the unembed stays
    # outside so the loss can stream it over sequence chunks
    if cfg.family == "encdec":
        return _forward_encdec(cfg, params, batch, flags)
    x = _embed(cfg, params, batch)
    aux = jnp.zeros((), jnp.float32)

    if cfg.family in ("dense", "moe"):
        x, aux = _scan_blocks(
            cfg, flags, x, params["layers"],
            lambda h, layer: _dense_block(cfg, flags, h, layer,
                                          causal=True, window=cfg.window))
    elif cfg.family == "ssm":
        def block(h, layer):
            from repro.sharding.rules import gather_fsdp
            layer = gather_fsdp(layer)
            inner = L.apply_norm(cfg, layer["ln1"], h, flags.use_pallas)
            out = h + R.apply_mamba_block(cfg, layer["mamba"], inner,
                                          flags.use_pallas)
            return (L.shard_hint(out, ("pod", "data"), "model", None),
                    jnp.zeros((), jnp.float32))
        x, aux = _scan_blocks(cfg, flags, x, params["layers"], block)
    elif cfg.family == "hybrid":
        kinds = hybrid_pattern(cfg)

        def hybrid_layer(h, layer, kind):
            from repro.sharding.rules import gather_fsdp
            layer = gather_fsdp(layer)
            inner = L.apply_norm(cfg, layer["ln1"], h, flags.use_pallas)
            if kind == "attn":
                mix = L.apply_attention(cfg, layer["attn"], inner, causal=True,
                                        window=cfg.window,
                                        use_pallas=flags.use_pallas,
                                        chunked=flags.chunked_attention)
            elif kind == "conv":
                mix = R.apply_short_conv(cfg, layer["conv"], inner)
            else:
                mix = R.apply_rglru_block(cfg, layer["rglru"], inner,
                                          flags.use_pallas)
            h, a, _ = _hybrid_ffn(cfg, flags, layer, h + mix)
            return L.shard_hint(h, ("pod", "data"), "model", None), a

        if flags.remat:
            # NOTE: prevent_cse must stay True here — the hybrid stack is an
            # unrolled python loop, and CSE would merge the rematerialized
            # values back with the forward ones, undoing the checkpoint
            # (prevent_cse=False is only safe inside scan bodies).
            hybrid_layer = jax.checkpoint(hybrid_layer, static_argnums=(2,))
        for i, layer in enumerate(params["layers"]):
            x, a = hybrid_layer(x, layer, kinds[i])
            aux = aux + a
    else:
        raise ValueError(cfg.family)

    x = L.apply_norm(cfg, params["ln_f"], x, flags.use_pallas)
    return x, aux


def _encode(cfg, params, batch, flags):
    enc = batch["frames"].astype(params["embed"].dtype)  # stub frontend output
    f = enc.shape[1]
    pos = jnp.arange(f)
    sin = _sinusoidal(pos, cfg.d_model).astype(enc.dtype)
    enc = enc + sin

    def enc_block(h, layer):
        return _dense_block(cfg, flags, h, layer, causal=False,
                            use_rope=False)[0], jnp.zeros((), jnp.float32)

    def body(carry, layer):
        h, aux = carry
        h2, a = enc_block(h, layer)
        return (h2, aux), None
    if flags.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (enc, _), _ = jax.lax.scan(body, (enc, jnp.zeros((), jnp.float32)),
                               params["enc_layers"])
    return L.apply_norm(cfg, params["enc_ln_f"], enc, flags.use_pallas)


def _decoder_block(cfg, flags, x, enc_out, layer):
    from repro.sharding.rules import gather_fsdp
    layer = gather_fsdp(layer)
    h = L.apply_attention(cfg, layer["attn"],
                          L.apply_norm(cfg, layer["ln1"], x, flags.use_pallas),
                          causal=True, use_rope=False,
                          use_pallas=flags.use_pallas,
                          chunked=flags.chunked_attention)
    x = x + h
    h = L.apply_attention(cfg, layer["xattn"],
                          L.apply_norm(cfg, layer["ln_x"], x, flags.use_pallas),
                          kv_x=enc_out, causal=False, use_rope=False,
                          use_pallas=flags.use_pallas,
                          chunked=flags.chunked_attention)
    x = x + h
    x = x + L.apply_mlp(cfg, layer["mlp"],
                        L.apply_norm(cfg, layer["ln2"], x, flags.use_pallas),
                        flags.use_pallas)
    return L.shard_hint(x, ("pod", "data"), "model", None)


def _forward_encdec(cfg, params, batch, flags):
    enc_out = _encode(cfg, params, batch, flags)
    tokens = batch["tokens"]
    s = tokens.shape[1]
    x = jnp.take(params["embed"], tokens, axis=0)
    x = x + jax.lax.dynamic_slice_in_dim(params["pos_embed"], 0, s, 0)

    def body(h, layer):
        return _decoder_block(cfg, flags, h, enc_out, layer), None
    if flags.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = L.apply_norm(cfg, params["ln_f"], x, flags.use_pallas)
    return x, jnp.zeros((), jnp.float32)


def _sinusoidal(pos, d):
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(10000.0) / max(half - 1, 1)))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ======================================================================
# loss
# ======================================================================

def lm_loss(cfg: ModelConfig, params, batch, flags: RuntimeFlags = DEFAULT_FLAGS,
            aux_weight: float = 0.01) -> jnp.ndarray:
    hidden, aux = forward_hidden(cfg, params, batch, flags)
    labels = batch["labels"]
    if hidden.shape[1] != labels.shape[1]:  # vlm prefix positions carry no loss
        hidden = hidden[:, hidden.shape[1] - labels.shape[1]:]
    b, s, _ = hidden.shape
    # streamed cross-entropy: the [B, S, V] logits tensor never materializes
    # in full — essential for the 150k-256k-vocab archs. Checkpointed scan
    # over sequence chunks; backward recomputes each chunk's logits.
    nc = flags.loss_chunks
    if nc <= 1 or s % nc != 0:
        nc = 1
    hs = jnp.moveaxis(hidden.reshape(b, nc, s // nc, hidden.shape[-1]), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, nc, s // nc), 1, 0)

    def chunk(carry, inp):
        tot, cnt = carry
        h, lab = inp
        logits = _unembed(cfg, params, h)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
        mask = (lab >= 0).astype(jnp.float32)
        return (tot + jnp.sum(nll * mask), cnt + jnp.sum(mask)), None

    body = jax.checkpoint(chunk, prevent_cse=False) if nc > 1 else chunk
    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hs, ls))
    loss = tot / jnp.maximum(cnt, 1.0)
    return loss + aux_weight * aux


# ======================================================================
# caches + decode
# ======================================================================

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    dh = cfg.resolved_head_dim
    if cfg.family in ("dense", "moe"):
        shape = (cfg.num_layers, batch, max_len, cfg.kv_heads, dh)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if cfg.family == "ssm":
        st = R.mamba_state_init(cfg, batch)
        return {"layers": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape), st)}
    if cfg.family == "hybrid":
        kinds = hybrid_pattern(cfg)
        w = min(cfg.window or max_len, max_len)
        cache = {}
        for i, kind in enumerate(kinds):
            if kind == "attn":
                cache[f"layer_{i}"] = {
                    "k": jnp.zeros((batch, w, cfg.kv_heads, dh), dtype),
                    "v": jnp.zeros((batch, w, cfg.kv_heads, dh), dtype)}
            elif kind == "conv":
                cache[f"layer_{i}"] = R.short_conv_state_init(cfg, batch)
            else:
                cache[f"layer_{i}"] = R.rglru_state_init(cfg, batch)
            if _holds_experts(cfg, i):
                cache[f"layer_{i}"].update(_expert_counters(cfg))
        return cache
    if cfg.family == "encdec":
        shape = (cfg.num_layers, batch, max_len, cfg.kv_heads, dh)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                "xk": None, "xv": None}  # cross-cache filled by prefill
    raise ValueError(cfg.family)


def decode_step(cfg: ModelConfig, params, cache: Dict, tokens: jnp.ndarray,
                position, flags: RuntimeFlags = DEFAULT_FLAGS
                ) -> Tuple[jnp.ndarray, Dict]:
    """One new token against the cache. tokens: [B, 1]; position: scalar."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.family == "encdec":
        pe = jax.lax.dynamic_slice_in_dim(params["pos_embed"], position, 1, 0)
        x = x + pe

    if cfg.family in ("dense", "moe", "encdec"):
        def body(h, inp):
            layer, ck, cv = inp[0], inp[1], inp[2]
            inner = L.apply_norm(cfg, layer["ln1"], h, flags.use_pallas)
            out, ck, cv = L.decode_attention_step(
                cfg, layer["attn"], inner, ck, cv, position,
                window=cfg.window, use_rope=cfg.family != "encdec",
                use_pallas=flags.use_pallas)
            h = h + out
            if cfg.family == "encdec":
                inner = L.apply_norm(cfg, layer["ln_x"], h, flags.use_pallas)
                h = h + _cross_decode(cfg, layer, inner, inp[3], inp[4])
            inner = L.apply_norm(cfg, layer["ln2"], h, flags.use_pallas)
            if cfg.moe:
                h = h + L.apply_moe(cfg, layer["moe"], inner, flags.use_pallas,
                                    capacity_factor=max(
                                        flags.moe_capacity_factor, 2.0))
            else:
                h = h + L.apply_mlp(cfg, layer["mlp"], inner, flags.use_pallas)
            return h, (ck, cv)

        xs = (params["layers"], cache["k"], cache["v"])
        if cfg.family == "encdec":
            xs = xs + (cache["xk"], cache["xv"])
        x, (k_new, v_new) = jax.lax.scan(lambda h, inp: body(h, inp), x, xs)
        cache = dict(cache, k=k_new, v=v_new)

    elif cfg.family == "ssm":
        def body(h, inp):
            layer, st = inp
            inner = L.apply_norm(cfg, layer["ln1"], h, flags.use_pallas)
            out, st = R.mamba_block_step(cfg, layer["mamba"], inner, st)
            return h + out, st
        x, new_states = jax.lax.scan(body, x, (params["layers"],
                                               cache["layers"]))
        cache = dict(cache, layers=new_states)

    elif cfg.family == "hybrid":
        kinds = hybrid_pattern(cfg)
        cache = dict(cache)
        for i, layer in enumerate(params["layers"]):
            entry = cache[f"layer_{i}"]
            inner = L.apply_norm(cfg, layer["ln1"], x, flags.use_pallas)
            if kinds[i] == "attn":
                out, ck, cv = L.decode_attention_step(
                    cfg, layer["attn"], inner, entry["k"], entry["v"],
                    position, window=cfg.window, use_pallas=flags.use_pallas)
                cache[f"layer_{i}"] = {"k": ck, "v": cv}
            elif kinds[i] == "conv":
                out, st = R.short_conv_step(cfg, layer["conv"], inner, entry)
                cache[f"layer_{i}"] = st
            else:
                out, st = R.rglru_block_step(cfg, layer["rglru"], inner, entry)
                cache[f"layer_{i}"] = st
            x, _, counts = _hybrid_ffn(cfg, flags, layer, x + out)
            if counts is not None:
                cache[f"layer_{i}"] = dict(cache[f"layer_{i}"],
                                           **_count_experts(entry, counts))
    else:
        raise ValueError(cfg.family)

    x = L.apply_norm(cfg, params["ln_f"], x, flags.use_pallas)
    return _unembed(cfg, params, x)[:, 0], cache


def _cross_decode(cfg, layer, x, xk, xv):
    """Cross-attention against the prefill-cached encoder KV."""
    import math as _m
    p = layer["xattn"]
    b = x.shape[0]
    dh = cfg.resolved_head_dim
    h, hkv = cfg.num_heads, cfg.kv_heads
    q = (x @ p["wq"]).reshape(b, 1, h, dh)
    g = h // hkv
    qg = q.reshape(b, hkv, g, dh).astype(jnp.float32) / _m.sqrt(dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, xk.astype(jnp.float32))
    pr = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", pr, xv.astype(jnp.float32))
    return (out.reshape(b, 1, h * dh).astype(x.dtype)) @ p["wo"]


def prefill(cfg: ModelConfig, params, batch: Dict,
            flags: RuntimeFlags = DEFAULT_FLAGS) -> Tuple[jnp.ndarray, Dict]:
    """Full-context forward that also returns the serving cache. A hybrid
    model's full-attention layers get a cache of ``cfg.max_seq_len``
    positions, room for the decode steps that follow."""
    if cfg.family in ("ssm", "hybrid"):
        return _prefill_recurrent(cfg, params, batch, flags)
    if cfg.family == "encdec":
        return _prefill_encdec(cfg, params, batch, flags)

    x = _embed(cfg, params, batch)
    s = x.shape[1]

    def body(h, layer):
        from repro.sharding.rules import gather_fsdp
        layer = gather_fsdp(layer)
        inner = L.apply_norm(cfg, layer["ln1"], h, flags.use_pallas)
        pos = jnp.arange(s)
        q, k, v = L._project_qkv(cfg, layer["attn"], inner, inner, pos, pos,
                                 True)
        out = L.grouped_attention(q, k, v, causal=True, window=cfg.window,
                                  chunked=flags.chunked_attention)
        h = h + out.reshape(*out.shape[:2], -1) @ layer["attn"]["wo"]
        inner = L.apply_norm(cfg, layer["ln2"], h, flags.use_pallas)
        if cfg.moe:
            h = h + L.apply_moe(cfg, layer["moe"], inner, flags.use_pallas,
                                capacity_factor=flags.moe_capacity_factor)
        else:
            h = h + L.apply_mlp(cfg, layer["mlp"], inner, flags.use_pallas)
        return h, (k, v)

    if flags.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    x = L.apply_norm(cfg, params["ln_f"], x[:, -1:], flags.use_pallas)
    logits = _unembed(cfg, params, x)[:, 0]
    return logits, {"k": ks, "v": vs}


def _prefill_recurrent(cfg, params, batch, flags):
    """SSM / hybrid prefill: run the sequence, keep final states (and the
    rolling attention window, or the padded KV cache, for hybrid)."""
    x = _embed(cfg, params, batch)
    bsz, s, _ = x.shape
    if cfg.family == "ssm":
        def body(h, layer):
            inner = L.apply_norm(cfg, layer["ln1"], h, flags.use_pallas)
            out, state = R.apply_mamba_block(cfg, layer["mamba"], inner,
                                             flags.use_pallas,
                                             return_state=True)
            return h + out, state
        if flags.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, states = jax.lax.scan(body, x, params["layers"])
        x = L.apply_norm(cfg, params["ln_f"], x[:, -1:], flags.use_pallas)
        return _unembed(cfg, params, x)[:, 0], {"layers": states}

    # hybrid
    kinds = hybrid_pattern(cfg)
    cache = {}
    for i, layer in enumerate(params["layers"]):
        inner = L.apply_norm(cfg, layer["ln1"], x, flags.use_pallas)
        if kinds[i] == "attn":
            pos = jnp.arange(s)
            q, k, v = L._project_qkv(cfg, layer["attn"], inner, inner, pos,
                                     pos, True)
            out = L.grouped_attention(q, k, v, causal=True, window=cfg.window,
                                      chunked=flags.chunked_attention)
            x = x + out.reshape(*out.shape[:2], -1) @ layer["attn"]["wo"]
            if cfg.window:
                w = min(cfg.window, s)
                cache[f"layer_{i}"] = {"k": k[:, -w:], "v": v[:, -w:]}
            else:
                room = ((0, 0), (0, cfg.max_seq_len - s), (0, 0), (0, 0))
                cache[f"layer_{i}"] = {"k": jnp.pad(k, room),
                                       "v": jnp.pad(v, room)}
        elif kinds[i] == "conv":
            out, cache[f"layer_{i}"] = R.apply_short_conv(
                cfg, layer["conv"], inner, return_state=True)
            x = x + out
        else:
            gate = jax.nn.gelu(inner @ layer["rglru"]["wgate"])
            u_raw = inner @ layer["rglru"]["wx"]
            u = R._causal_conv(u_raw, layer["rglru"]["conv_w"],
                               layer["rglru"]["conv_b"], cfg.conv_width)
            a, gin = R._rglru_gates(layer["rglru"], u)
            hseq = R.rglru_scan(a, gin)
            x = x + (hseq.astype(x.dtype) * gate) @ layer["rglru"]["wo"]
            cache[f"layer_{i}"] = {
                "conv": u_raw[:, -(cfg.conv_width - 1):].astype(jnp.float32),
                "h": hseq[:, -1]}
        x, _, counts = _hybrid_ffn(cfg, flags, layer, x)
        if counts is not None:
            cache[f"layer_{i}"].update(
                _count_experts(_expert_counters(cfg), counts))
    x = L.apply_norm(cfg, params["ln_f"], x[:, -1:], flags.use_pallas)
    return _unembed(cfg, params, x)[:, 0], cache


def _prefill_encdec(cfg, params, batch, flags):
    enc_out = _encode(cfg, params, batch, flags)
    tokens = batch["tokens"]
    s = tokens.shape[1]
    x = jnp.take(params["embed"], tokens, axis=0)
    x = x + jax.lax.dynamic_slice_in_dim(params["pos_embed"], 0, s, 0)
    dh = cfg.resolved_head_dim

    def body(h, layer):
        inner = L.apply_norm(cfg, layer["ln1"], h, flags.use_pallas)
        pos = jnp.arange(s)
        q, k, v = L._project_qkv(cfg, layer["attn"], inner, inner, pos, pos,
                                 False)
        out = L.grouped_attention(q, k, v, causal=True, window=None,
                                  chunked=flags.chunked_attention)
        h = h + out.reshape(*out.shape[:2], -1) @ layer["attn"]["wo"]
        inner = L.apply_norm(cfg, layer["ln_x"], h, flags.use_pallas)
        p = layer["xattn"]
        fpos = jnp.arange(enc_out.shape[1])
        qx, kx, vx = L._project_qkv(cfg, p, inner, enc_out,
                                    jnp.arange(s), fpos, False)
        xout = L.grouped_attention(qx, kx, vx, causal=False, window=None,
                                   chunked=flags.chunked_attention)
        h = h + xout.reshape(*xout.shape[:2], -1) @ p["wo"]
        h = h + L.apply_mlp(cfg, layer["mlp"],
                            L.apply_norm(cfg, layer["ln2"], h,
                                         flags.use_pallas),
                            flags.use_pallas)
        return h, (k, v, kx, vx)

    if flags.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, (ks, vs, xks, xvs) = jax.lax.scan(body, x, params["layers"])
    x = L.apply_norm(cfg, params["ln_f"], x[:, -1:], flags.use_pallas)
    return _unembed(cfg, params, x)[:, 0], {"k": ks, "v": vs,
                                            "xk": xks, "xv": xvs}
