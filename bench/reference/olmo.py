"""Plain OLMo-1B: weights from the seed, and a float32 forward pass.

``make_weights`` is the benchmark's own weight generator. It lays the
weights out as the program's parameter tree expects them (the system under
test is handed these arrays), and the reference calls it again, from the same
seed, once the program's state is freed: the reference takes nothing the
program made.

``logits`` is the architecture's forward pass in straightforward
``jax.numpy`` at float32 and ``Precision.HIGHEST``, with no kernels, no
cache and no batching tricks (arXiv:2402.00838): non-parametric LayerNorm
(eps 1e-5), rotary embeddings on the two halves of each head (theta 1e4),
causal multi-head attention, a SwiGLU MLP whose SiLU acts on ``wi`` and
whose gate is ``wg``, and an unembedding tied to the embedding.
``quant="fp8"`` runs the same pass with both operands of every matrix
product rounded to float8_e4m3fn (per-row and per-column scales), which is
the control: the nearest precision below the bfloat16 that serving states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _shapes(cfg: dict) -> dict:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    nl = cfg["num_hidden_layers"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"embed": (v, d),
            "wq": (nl, d, hd), "wk": (nl, d, kvd), "wv": (nl, d, kvd),
            "wo": (nl, hd, d),
            "wi": (nl, d, f), "wg": (nl, d, f), "w_down": (nl, f, d)}


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(cfg_items: tuple, seed_words, dtype):
    cfg = dict(cfg_items)
    key = seeded_key(seed_words)
    out = {}
    for i, (name, shape) in enumerate(sorted(_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        std = 0.02 if name == "embed" else 1.0 / np.sqrt(shape[-2])
        out[name] = jax.random.normal(k, shape, dtype) * jnp.asarray(std,
                                                                     dtype)
    return out


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Seeded weights in the program's parameter layout, made on the
    device in one jitted call, drawn directly in ``dtype``. ``seed`` may be
    any integer up to 2**64."""
    w = _make(_items(cfg), seed_words(seed), jnp.dtype(dtype))
    return {"embed": w["embed"], "ln_f": {},
            "layers": {"ln1": {}, "ln2": {},
                       "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                                "wo": w["wo"]},
                       "mlp": {"wi": w["wi"], "wg": w["wg"],
                               "wo": w["w_down"]}}}


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two 32-bit words, passed to jitted code as
    an array so that every seed runs the same compiled program."""
    seed = int(seed) % 2**64
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def seeded_key(words):
    """A PRNG key from :func:`seed_words` (traceable)."""
    return jax.random.fold_in(jax.random.key(words[0]), words[1])


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------

def _q8(x, axis):
    """Round to float8_e4m3fn with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _mm(a, b, quant):
    """a[..., k] @ b[k, n] in float32 at HIGHEST; with ``quant`` both
    operands are first rounded to fp8 (a per row, b per column)."""
    if quant == "fp8":
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _layernorm(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5)


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


@functools.partial(jax.jit, static_argnums=(0, 3))
def _forward(cfg_items: tuple, w: dict, tokens, quant):
    cfg = dict(cfg_items)
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    hkv = cfg["num_key_value_heads"]
    b, s = tokens.shape
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(w["embed"])[tokens]
    causal = np.tril(np.ones((s, s), bool))

    def layer(x, lw):
        y = _layernorm(x)
        q = _mm(y, f32(lw["wq"]), quant).reshape(b, s, h, dh)
        k = _mm(y, f32(lw["wk"]), quant).reshape(b, s, hkv, dh)
        v = _mm(y, f32(lw["wv"]), quant).reshape(b, s, hkv, dh)
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
        sc = jnp.where(causal, sc / np.sqrt(dh), -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
        x = x + _mm(a.reshape(b, s, h * dh), f32(lw["wo"]), quant)
        y = _layernorm(x)
        g = jax.nn.silu(_mm(y, f32(lw["wi"]), quant)) \
            * _mm(y, f32(lw["wg"]), quant)
        return x + _mm(g, f32(lw["w_down"]), quant), None

    stacked = {"wq": w["layers"]["attn"]["wq"], "wk": w["layers"]["attn"]["wk"],
               "wv": w["layers"]["attn"]["wv"], "wo": w["layers"]["attn"]["wo"],
               "wi": w["layers"]["mlp"]["wi"], "wg": w["layers"]["mlp"]["wg"],
               "w_down": w["layers"]["mlp"]["wo"]}
    x, _ = jax.lax.scan(layer, x, stacked)
    return _mm(_layernorm(x), f32(w["embed"]).T, quant)


def _items(cfg: dict) -> tuple:
    """The configuration's scalar entries, hashable for ``jit``."""
    return tuple((k, v) for k, v in sorted(cfg.items())
                 if isinstance(v, (int, float, str, bool)) or v is None)


def logits(cfg: dict, w: dict, tokens, quant=None):
    """float32 logits [b, s, vocab] for ``tokens`` [b, s]."""
    items = _items(cfg)
    with jax.default_matmul_precision("highest"):
        return _forward(items, w, jnp.asarray(tokens, jnp.int32), quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _gaps(cfg_items: tuple, w: dict, tokens, control: bool):
    ref = _forward(cfg_items, w, tokens, None)[:, :-1]
    top = jnp.max(ref, axis=-1)
    nxt = jnp.take_along_axis(ref, tokens[:, 1:, None], axis=-1)[..., 0]
    out = {"served": top - nxt}
    if control:
        ctl = _forward(cfg_items, w, tokens, "fp8")[:, :-1]
        pick = jnp.argmax(ctl, axis=-1)
        out["control"] = top - jnp.take_along_axis(
            ref, pick[..., None], axis=-1)[..., 0]
    return out


def gaps(cfg: dict, w: dict, tokens, control: bool = False) -> dict:
    """For each position t < s-1 of ``tokens`` [b, s]: how far the
    reference's logit of token t+1 lies below its best logit ("served"),
    and, with ``control``, how far the reference's logit of the token that
    the fp8 pass puts first lies below it ("control")."""
    with jax.default_matmul_precision("highest"):
        out = _gaps(_items(cfg), w, jnp.asarray(tokens, jnp.int32), control)
    return {k: np.asarray(v) for k, v in out.items()}
