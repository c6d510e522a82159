"""The plain references against the program's own path, at a small size
on the CPU: the same weights and operands give the same answers."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import kernels, serve
from bench.reference import l2, olmo
from tests_common import TINY_OLMO

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def tiny_olmo():
    cfg = json.loads((CONFIGS / "olmo-1b.json").read_text())
    cfg.update(TINY_OLMO)
    return cfg


def test_olmo_reference_matches_program_forward():
    from repro.models.model import RuntimeFlags, forward
    cfg = tiny_olmo()
    w = olmo.make_weights(cfg, 2**40 + 9, jnp.float32)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 24))
    with jax.default_matmul_precision("highest"):
        got, _ = forward(serve.program_config(cfg), w,
                         {"tokens": jnp.asarray(toks)},
                         RuntimeFlags(remat=False))
    want = olmo.logits(cfg, w, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_weights_repeat_for_a_seed_and_differ_across_seeds():
    cfg = tiny_olmo()
    a = olmo.make_weights(cfg, 2**33 + 1)
    b = olmo.make_weights(cfg, 2**33 + 1)
    c = olmo.make_weights(cfg, 1)
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                       jax.tree.leaves(c)):
        assert x.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(x), np.asarray(y))
        assert not np.array_equal(np.asarray(x), np.asarray(z))


def test_served_gap_is_zero_for_the_reference_argmax():
    cfg = tiny_olmo()
    w = olmo.make_weights(cfg, 3)
    toks = np.random.default_rng(1).integers(0, cfg["vocab_size"], (1, 8))
    # extend greedily with the reference's own choices: every gap is 0
    for _ in range(4):
        nxt = np.asarray(olmo.logits(cfg, w, toks))[0, -1].argmax()
        toks = np.concatenate([toks, [[nxt]]], axis=1)
    g = olmo.gaps(cfg, w, toks, control=True)["served"][0, 7:]
    assert np.all(g == 0.0)


@pytest.mark.parametrize("name", [p["name"] for p in json.loads(
    (CONFIGS / "aibench-l2-gemm.json").read_text())["problems"]])
def test_l2_reference_matches_program_graph(name):
    from repro.aibench.spec import load_specs
    from repro.aibench.suite import build_program
    from repro.core.executor import run_program
    cfg = json.loads((CONFIGS / "aibench-l2-gemm.json").read_text())
    cfg["dims"] = {"M": 128, "N": 256, "K": 128}
    problem = next(p for p in cfg["problems"] if p["name"] == name)
    spec = next(s for s in load_specs() if s.name == name)
    prog = build_program(spec.builder, cfg["dims"], "eager")
    prog.schedule.compute_dtype = "float32"
    ops = kernels.operands(cfg, problem, 5)
    ins, params = kernels.split(prog, ops)
    with jax.default_matmul_precision("highest"):
        (_, got), = run_program(prog, ins, params).items()
    assert kernels.rel_error(got, l2.output(name, ops)) < 1e-5
