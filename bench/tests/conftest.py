"""CPU rehearsal helpers: tiny sizes of every cell, run through the harness
with the chip check switched off."""

import copy
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from tests_common import TINY_OLMO  # noqa: E402
TINY_MIX = {
    "olmo-1b.serve.chat": {"slots": 4, "kv_len": 128, "prompt_len": 8,
                           "answer_len": {"min": 2, "max": 4},
                           "arrivals": {"kind": "poisson", "rate": 8.0},
                           "check": {"requests": 8, "block": 4,
                                     "min_tokens": 4, "max_logit_gap": 0.5}},
    "olmo-1b.serve.batch": {"slots": 4, "kv_len": 128, "prompt_len": 4,
                            "answer_len": {"min": 2, "max": 6},
                            "arrivals": {"kind": "backlog", "requests": 64},
                            "check": {"requests": 4, "block": 2,
                                      "min_tokens": 4,
                                      "max_logit_gap": 0.5}},
}
TINY_L2 = {"dims": {"M": 256, "N": 256, "K": 128}}
L2_SUBSET = ("gemm_bias_gelu", "gemm_divide_sum", "matmul_t_gelu",
             "gemm_scale_residual")


def tiny_cell(name: str, load) -> dict:
    """The cell as ``load`` (``bench.run.load_cell``) gives it, cut to a
    CPU size."""
    cell = copy.deepcopy(load(name))
    if cell["mix"]["harness"] == "serve":
        cell["config"].update(TINY_OLMO)
        cell["mix"].update(copy.deepcopy(TINY_MIX[name]))
    else:
        cell["config"].update(TINY_L2)
        cell["config"]["problems"] = [p for p in cell["config"]["problems"]
                                      if p["name"] in L2_SUBSET]
    return cell


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    """Run a cell at its tiny size on the CPU: returns run(name, seed,
    seconds, trace) -> result. The Forge store goes to a temporary
    directory, and ``kernels`` problems to the ci dims of their specs."""
    from bench import peaks, run
    from bench.harness import kernels

    load = run.load_cell
    monkeypatch.setattr(run, "load_cell", lambda name: tiny_cell(name, load))
    monkeypatch.setattr(run, "CACHE", tmp_path)
    monkeypatch.setattr(run, "_device_check", cpu_device)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])

    def spec_dims(orig):
        def forge_programs(cfg, store_dir):
            from repro.aibench import spec as sp
            real = sp.ProblemSpec.dims
            monkeypatch.setattr(sp.ProblemSpec, "dims",
                                lambda self, v: real(self, "ci"))
            return orig(cfg, store_dir)
        return forge_programs
    monkeypatch.setattr(kernels, "forge_programs",
                        spec_dims(kernels.forge_programs))

    def go(name, seed=7, seconds=1.0, trace=False):
        return run.run_cell(name, seed, seconds, trace)
    return go


def cpu_device(chips):
    """Stands in for the chip check: the CPU, with v5e peaks (tests only)."""
    import jax
    return jax.devices()[0]
