"""Plain LFM2-8B-A1B at one chip's expert share: weights from the seed, and
a float32 forward pass.

``make_weights`` is the benchmark's own weight generator, in the program's
parameter layout (the system under test is handed these arrays); the
reference calls it again, from the same seed, once the program's state is
freed. The configuration's ``num_experts`` experts are held, the first being
``first_expert``, of the router's ``num_experts_published`` outputs.

``logits`` is the architecture's forward pass in straightforward
``jax.numpy`` at float32 and ``Precision.HIGHEST``, with no kernels, no cache
and no dispatch buffers, one layer at a time. Pre-norm RMSNorm (eps
``norm_eps``) before each mixer and each feed-forward, and before the
logits, which are taken against the embedding:

- conv layers: ``B, C, x = split(W_in u)``; ``v = B * x``; a causal
  depthwise conv of ``conv_L_cache`` taps over time,
  ``c_t = sum_j w_j * v_{t-L+1+j}``; output ``W_out (C * c)``;
- attention layers: GQA, q and k each through a per-head RMSNorm, then
  rotary embeddings on the two halves of each head (theta ``rope_theta``),
  causal softmax;
- the first ``num_dense_layers`` feed-forwards: ``W2 (silu(W1 u) * W3 u)``;
- the others: ``s = sigmoid(u W_r)`` over every expert, the top
  ``num_experts_per_tok`` by ``s + expert_bias``, each gated by
  ``s_e / (sum of the chosen s + 1e-6) * routed_scaling_factor``; of the sum
  over the chosen experts, the part of the held ones, each computed for
  every token and weighed by its gate (zero where not chosen).

Departures from the published description: the experts that are not held
are left out (under expert parallelism other chips add their part); the
conv, the norms and every product run in float32, where the published model
runs in bfloat16; tied embeddings and the qk-norm eps (``norm_eps``) are
assumed, as the configuration file says.

``quant="fp8"`` runs the same pass with both operands of every product with a
weight matrix (router and logits included) rounded to float8_e4m3fn
(per-row and per-column scales): the control, the nearest precision below
the bfloat16 that serving states. ``quant="bf16"`` rounds them to bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.olmo import HIGHEST, _q8, seed_words, seeded_key


def mixer_kinds(cfg: dict) -> list:
    return ["attn" if t == "full_attention" else "conv"
            for t in cfg["layer_types"]]


def _leaves(cfg: dict) -> tuple:
    """(path, shape, init, dtype name) of every weight, in a fixed order;
    init is a standard deviation, or "norm" (1 + N(0, 0.1))."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, width = d // h, cfg["conv_L_cache"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e, held = cfg["num_experts_published"], cfg["num_experts"]
    out = [(("embed",), (v, d), 0.02, "w"), (("ln_f", "scale"), (d,), "norm", "w")]
    for i, kind in enumerate(mixer_kinds(cfg)):
        lay = ("layers", i)
        out += [(lay + ("ln1", "scale"), (d,), "norm", "w"),
                (lay + ("ln2", "scale"), (d,), "norm", "w")]
        if kind == "attn":
            out += [(lay + ("attn", "wq"), (d, h * dh), d ** -0.5, "w"),
                    (lay + ("attn", "wk"), (d, hkv * dh), d ** -0.5, "w"),
                    (lay + ("attn", "wv"), (d, hkv * dh), d ** -0.5, "w"),
                    (lay + ("attn", "wo"), (h * dh, d), (h * dh) ** -0.5, "w"),
                    (lay + ("attn", "q_norm"), (dh,), "norm", "w"),
                    (lay + ("attn", "k_norm"), (dh,), "norm", "w")]
        else:
            out += [(lay + ("conv", "w_in"), (d, 3 * d), d ** -0.5, "w"),
                    (lay + ("conv", "conv_w"), (width, d), width ** -0.5, "w"),
                    (lay + ("conv", "w_out"), (d, d), d ** -0.5, "w")]
        if i < cfg["num_dense_layers"]:
            out += [(lay + ("mlp", "wi"), (d, f), d ** -0.5, "w"),
                    (lay + ("mlp", "wg"), (d, f), d ** -0.5, "w"),
                    (lay + ("mlp", "wo"), (f, d), f ** -0.5, "w")]
        else:
            out += [(lay + ("moe", "router"), (d, e), d ** -0.5, "w"),
                    (lay + ("moe", "expert_bias"), (e,), 0.02, "f32"),
                    (lay + ("moe", "wi"), (held, d, fe), d ** -0.5, "w"),
                    (lay + ("moe", "wg"), (held, d, fe), d ** -0.5, "w"),
                    (lay + ("moe", "wo"), (held, fe, d), fe ** -0.5, "w")]
    return tuple(out)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(leaves: tuple, words, dtype):
    key = seeded_key(words)
    out = []
    for i, (_, shape, init, kind) in enumerate(leaves):
        dt = jnp.float32 if kind == "f32" else dtype
        z = jax.random.normal(jax.random.fold_in(key, i), shape, dt)
        out.append(1 + z * jnp.asarray(0.1, dt) if init == "norm"
                   else z * jnp.asarray(init, dt))
    return out


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Seeded weights in the program's parameter layout (a list of layers),
    made on the device in one jitted call, drawn directly in ``dtype``
    (the expert bias in float32, as the program keeps it). ``seed`` may be
    any integer up to 2**64."""
    leaves = _leaves(cfg)
    arrays = _make(leaves, seed_words(seed), jnp.dtype(dtype))
    tree: dict = {"layers": [{} for _ in cfg["layer_types"]]}
    for (path, *_), a in zip(leaves, arrays):
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {}) if isinstance(k, str) else node[k]
        node[path[-1]] = a
    return tree


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------

def _mm(a, b, quant):
    """a[..., k] @ b[k, n] in float32 at HIGHEST; with ``quant`` both
    operands are first rounded (fp8: a per row, b per column)."""
    if quant == "fp8":
        a, b = _q8(a, -1), _q8(b, 0)
    elif quant == "bf16":
        a, b = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (a, b))
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x: [b, s, h, dh], rotated by position along s."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float32) * 2.0 / dh))
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _conv(cfg, w, y, quant):
    b_, c_, xx = jnp.split(_mm(y, w["w_in"], quant), 3, axis=-1)
    v = b_ * xx
    width, s = w["conv_w"].shape[0], v.shape[1]
    pad = jnp.pad(v, ((0, 0), (width - 1, 0), (0, 0)))
    c = sum(w["conv_w"][j] * pad[:, j:j + s] for j in range(width))
    return _mm(c_ * c, w["w_out"], quant)


def _attn(cfg, w, y, quant):
    b, s, _ = y.shape
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps = cfg["hidden_size"] // h, cfg["norm_eps"]
    q = _rmsnorm(_mm(y, w["wq"], quant).reshape(b, s, h, dh), w["q_norm"], eps)
    k = _rmsnorm(_mm(y, w["wk"], quant).reshape(b, s, hkv, dh), w["k_norm"],
                 eps)
    v = _mm(y, w["wv"], quant).reshape(b, s, hkv, dh)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    sc = jnp.where(np.tril(np.ones((s, s), bool)), sc / np.sqrt(dh), -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                   precision=HIGHEST)
    return _mm(a.reshape(b, s, h * dh), w["wo"], quant)


def _swiglu(y, wi, wg, wo, quant):
    return _mm(jax.nn.silu(_mm(y, wi, quant)) * _mm(y, wg, quant), wo, quant)


def moe_share(cfg: dict, w: dict, y, quant=None):
    """The held experts' part of an expert layer, for float32 ``y``
    [..., d]: routing over all ``num_experts_published`` experts, and each
    held expert computed for every token, weighed by its gate (0 where the
    token did not choose it)."""
    k, held = cfg["num_experts_per_tok"], cfg["num_experts"]
    s = jax.nn.sigmoid(_mm(y, w["router"], quant))
    _, idx = jax.lax.top_k(s + w["expert_bias"], k)
    g = jnp.take_along_axis(s, idx, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6)
    g = g * cfg["routed_scaling_factor"]
    gate = jnp.einsum("...k,...ke->...e", g,
                      jax.nn.one_hot(idx - cfg["first_expert"], held),
                      precision=HIGHEST)
    out = 0.0
    for e in range(held):
        out = out + gate[..., e:e + 1] * _swiglu(
            y, w["wi"][e], w["wg"][e], w["wo"][e], quant)
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 5))
def _layer(cfg_items: tuple, kind: str, sparse: bool, lw: dict, x, quant):
    cfg = dict(cfg_items)
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    lw, eps = f32(lw), cfg["norm_eps"]
    y = _rmsnorm(x, lw["ln1"]["scale"], eps)
    x = x + (_attn(cfg, lw["attn"], y, quant) if kind == "attn"
             else _conv(cfg, lw["conv"], y, quant))
    y = _rmsnorm(x, lw["ln2"]["scale"], eps)
    if sparse:
        return x + moe_share(cfg, lw["moe"], y, quant)
    m = lw["mlp"]
    return x + _swiglu(y, m["wi"], m["wg"], m["wo"], quant)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits(cfg_items: tuple, embed, scale, x, quant):
    cfg = dict(cfg_items)
    e = embed.astype(jnp.float32)
    return _mm(_rmsnorm(x, scale.astype(jnp.float32), cfg["norm_eps"]), e.T,
               quant)


def _items(cfg: dict) -> tuple:
    """The configuration's scalar entries, hashable for ``jit``."""
    return tuple((k, v) for k, v in sorted(cfg.items())
                 if isinstance(v, (int, float, str, bool)) or v is None)


def logits(cfg: dict, w: dict, tokens, quant=None):
    """float32 logits [b, s, vocab] for ``tokens`` [b, s]."""
    items = _items(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = w["embed"].astype(jnp.float32)[tokens]
        for i, kind in enumerate(mixer_kinds(cfg)):
            x = _layer(items, kind, i >= cfg["num_dense_layers"],
                       w["layers"][i], x, quant)
        return _logits(items, w["embed"], w["ln_f"]["scale"], x, quant)


def gaps(cfg: dict, w: dict, tokens, control: bool = False) -> dict:
    """For each position t < s-1 of ``tokens`` [b, s]: how far the
    reference's logit of token t+1 lies below its best logit ("served"),
    and, with ``control``, how far the reference's logit of the token that
    the fp8 pass puts first lies below it ("control")."""
    tokens = jnp.asarray(tokens, jnp.int32)
    ref = logits(cfg, w, tokens)[:, :-1]
    top = jnp.max(ref, axis=-1)
    nxt = jnp.take_along_axis(ref, tokens[:, 1:, None], axis=-1)[..., 0]
    out = {"served": np.asarray(top - nxt)}
    if control:
        pick = jnp.argmax(logits(cfg, w, tokens, "fp8")[:, :-1], axis=-1)
        out["control"] = np.asarray(top - jnp.take_along_axis(
            ref, pick[..., None], axis=-1)[..., 0])
    return out
