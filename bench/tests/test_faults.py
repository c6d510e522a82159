"""The comparison catches a broken timed path: each fault the cell can have
is planted under a tiny run, and ``correct`` comes out false."""

import jax.numpy as jnp
import pytest


def _wrap_decode(monkeypatch, fault):
    import repro.serve.engine as eng
    real = eng.decode_step

    def broken(cfg, params, cache, tokens, pos, flags):
        logits, new = real(cfg, params, cache, tokens, pos, flags)
        return fault(logits, cache, new)
    monkeypatch.setattr(eng, "decode_step", broken)


SERVE_FAULTS = {
    # a step that returns its state (the KV cache) unchanged
    "state_unchanged": lambda logits, old, new: (logits, old),
    # a token altered where it is produced
    "token_altered": lambda logits, old, new: (logits.at[:, 1].add(1e4), new),
    # half of the batch left out: the first half's answers copied over
    "half_batch": lambda logits, old, new: (
        jnp.concatenate([logits[: logits.shape[0] // 2]] * 2), new),
}


@pytest.mark.parametrize("fault", sorted(SERVE_FAULTS))
def test_serve_fault_is_caught(tiny, monkeypatch, fault):
    _wrap_decode(monkeypatch, SERVE_FAULTS[fault])
    assert tiny("olmo-1b.serve.batch", seed=11)["correct"] is False


def _alter(v):
    return v.at[(0,) * v.ndim].add(0.1 * jnp.max(jnp.abs(v)))


def _half(v):
    h = v.shape[0] // 2
    return jnp.concatenate([v[:h], v[:h]])


@pytest.mark.parametrize("fault", [_alter, _half])
def test_kernel_answer_fault_is_caught(tiny, monkeypatch, fault):
    import repro.core.executor as ex
    real = ex.run_program

    def broken(program, inputs, params, use_pallas=True):
        return {k: fault(v) for k, v in
                real(program, inputs, params, use_pallas).items()}
    monkeypatch.setattr(ex, "run_program", broken)
    assert tiny("forge.l2-gemm", seed=11)["correct"] is False
