"""LFM2 at a small size on the CPU: serving through ``ServeEngine`` against
the plain float32 reference (``bench/reference/lfm2.py``), the expert
layer's shares against the uncut layer, the grouped-expert kernel against
its oracle, and the short conv's decode step against its full sequence."""

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness.serve_lfm2 import program_config  # noqa: E402
from bench.reference import lfm2 as ref  # noqa: E402
from repro.kernels.moe_experts import moe_experts  # noqa: E402
from repro.kernels.ref import moe_experts_ref  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import recurrent as R  # noqa: E402
from repro.models.model import RuntimeFlags  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402

# d 64; conv, conv, attention, conv; two dense layers, then experts: 8
# routed, top-2, 4 held here
TINY = {"hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32,
        "layer_types": ["conv", "conv", "full_attention", "conv"],
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 256, "num_experts": 4,
        "num_experts_published": 8, "num_experts_per_tok": 2,
        "first_expert": 0, "num_dense_layers": 2}

# Serving's logits against the reference's, as max |diff| / max |ref|. In
# float32 the two differ by the order of their sums (the engine's one-token
# steps against the reference's whole sequence, Pallas kernels against
# jnp.matmul): 3.6e-7 here. Rounding every product's operands to bfloat16
# moves them by 0.36 (some tokens pick other experts), so 1e-4 passes the
# first with room and fails the second.
SERVE_TOL = 1e-4


def tiny_cfg(**kw) -> dict:
    cfg = json.loads((ROOT / "bench" / "configs" / "lfm2-8b-a1b.json")
                     .read_text())
    cfg.update(TINY, **kw)
    return cfg


def _rel(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want)))
                 / np.max(np.abs(np.asarray(want))))


def test_serving_matches_the_reference_forward():
    cfg = tiny_cfg()
    w = ref.make_weights(cfg, 2**40 + 3, jnp.float32)
    engine = ServeEngine(program_config(cfg), w, max_len=24, slots=3,
                         flags=RuntimeFlags(use_pallas=True, remat=False))
    steps, decode = [], engine._decode

    def record(p, c, t, pos):
        logits, c = decode(p, c, t, pos)
        steps.append(np.asarray(logits))
        return logits, c
    engine._decode = record
    rng = np.random.default_rng(0)
    for rid, n in enumerate((5, 9)):
        engine.submit(Request(rid=rid, prompt=rng.integers(0, 256, 6,
                                                           dtype=np.int32),
                              max_new_tokens=n))
    done = engine.run()
    assert len(steps) == 6 + 9 - 1

    seqs = np.stack([np.concatenate([r.prompt, r.generated,
                                     np.zeros(9 - len(r.generated), int)])
                     for r in done])
    want = np.asarray(ref.logits(cfg, w, seqs))
    bf16 = np.asarray(ref.logits(cfg, w, seqs, quant="bf16"))
    for i, r in enumerate(done):
        live = len(r.prompt) + len(r.generated) - 1
        got = np.stack([s[i] for s in steps[:live]])
        assert _rel(got, want[i, :live]) < SERVE_TOL
        # the served tokens are the reference's own greedy choices
        assert list(want[i, 5:live].argmax(-1)) == r.generated
    # the tolerance tells float32 from a bfloat16 pass of the reference
    assert _rel(bf16, want) > 10 * SERVE_TOL


@pytest.mark.parametrize("skewed", [False, True])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_expert_shares_add_up_to_the_uncut_layer(use_pallas, skewed):
    """Each chip's share routes over all 8 experts and computes its own 4;
    the two shares sum to the whole layer. Skewed: every token picks the
    same two held experts, and none of their pairs is dropped."""
    whole = tiny_cfg(num_experts=8)
    layer = ref.make_weights(whole, 11, jnp.float32)["layers"][2]["moe"]
    if skewed:
        layer = dict(layer, expert_bias=layer["expert_bias"].at[1:3].add(9.0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 7, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.moe_share(whole, layer, x)
        got = 0.0
        for first in (0, 4):
            share = dict(layer, **{k: layer[k][first:first + 4]
                                   for k in ("wi", "wg", "wo")})
            pcfg = program_config(tiny_cfg(first_expert=first))
            got = got + L.apply_moe(pcfg, share, x, use_pallas)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,counts", [
    (jnp.float32, (5, 0, 16, 3)), (jnp.bfloat16, (5, 0, 16, 3)),
    (jnp.float32, (0, 0, 9, 0))])
def test_moe_experts_kernel_matches_oracle(dtype, counts):
    """Two row blocks and two intermediate tiles per expert; an expert with
    no tokens (and, in the last case, the first two and the last), one
    that fills a part of a block. An empty expert's blocks point at those
    of the last expert with rows, which it must not compute with."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    e, c, d, f = 4, 16, 128, 256
    x = jax.random.normal(ks[0], (e, c, d), jnp.float32).astype(dtype)
    wi = (jax.random.normal(ks[1], (e, d, f)) * d ** -0.5).astype(dtype)
    wg = (jax.random.normal(ks[2], (e, d, f)) * d ** -0.5).astype(dtype)
    wo = (jax.random.normal(ks[3], (e, f, d)) * f ** -0.5).astype(dtype)
    counts = jnp.array(counts, jnp.int32)
    got = moe_experts(x, counts, wi, wg, wo, block_f=128, block_rows=8,
                      interpret=True)
    with jax.default_matmul_precision("highest"):
        want = moe_experts_ref(x, counts, wi, wg, wo)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    got = np.asarray(got, np.float32)
    np.testing.assert_allclose(got, np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    for ex, n in enumerate(np.asarray(counts)):
        assert not got[ex, n:].any()
        assert n == 0 or got[ex, :n].any()


def test_decode_counts_the_held_experts_work():
    """Every token picks held experts 1 and 2 (a large bias): each expert
    layer's counters read the rows of every step and the calls with rows,
    and prefill counts its call once."""
    from repro.models.model import decode_step, init_cache, prefill
    cfg = tiny_cfg()
    pcfg = program_config(cfg)
    w = ref.make_weights(cfg, 7, jnp.float32)
    moe = range(cfg["num_dense_layers"], cfg["num_hidden_layers"])
    for i in moe:
        m = w["layers"][i]["moe"]
        m["expert_bias"] = m["expert_bias"].at[1:3].add(9.0)
    flags = RuntimeFlags(use_pallas=False, remat=False)
    step = jax.jit(lambda c, t, pos: decode_step(pcfg, w, c, t, pos, flags))
    cache = init_cache(pcfg, 3, 16, jnp.float32)
    for pos in range(4):
        _, cache = step(cache, jnp.full((3, 1), pos + 1, jnp.int32), pos)
    _, pre = prefill(pcfg, w, {"tokens": jnp.ones((3, 5), jnp.int32)}, flags)
    assert jax.tree.structure(pre) == jax.tree.structure(cache)
    for i in range(cfg["num_hidden_layers"]):
        entry = cache[f"layer_{i}"]
        if i not in moe:
            assert "expert_rows" not in entry
            continue
        assert list(entry["expert_rows"]) == [0, 12, 12, 0]
        assert list(entry["expert_calls"]) == [0, 4, 4, 0]
        assert list(pre[f"layer_{i}"]["expert_rows"]) == [0, 15, 15, 0]
        assert list(pre[f"layer_{i}"]["expert_calls"]) == [0, 1, 1, 0]


def test_short_conv_step_matches_full_sequence():
    cfg = program_config(tiny_cfg())
    p = R.init_short_conv(cfg, jax.random.PRNGKey(2), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 7, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        full, final = R.apply_short_conv(cfg, p, x, return_state=True)
        state, outs = R.short_conv_state_init(cfg, 2), []
        for t in range(7):
            out, state = R.short_conv_step(cfg, p, x[:, t:t + 1], state)
            outs.append(out)
    assert state["conv"].shape == (2, cfg.conv_width - 1, 64) == (2, 2, 64)
    np.testing.assert_allclose(np.concatenate(outs, axis=1), full,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(state["conv"], final["conv"], rtol=1e-6)


def test_norm_eps_comes_from_the_config():
    from repro.configs.base import get_config
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 64)) * 1e-3
    scale = {"scale": jnp.ones((64,))}
    lfm2 = program_config(tiny_cfg())
    assert lfm2.resolved_norm_eps == 1e-5
    assert get_config("qwen3-8b").resolved_norm_eps == 1e-6
    assert get_config("olmo-1b").resolved_norm_eps == 1e-5
    want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    for use_pallas in (False, True):
        np.testing.assert_allclose(L.apply_norm(lfm2, scale, x, use_pallas),
                                   want, rtol=1e-5)
