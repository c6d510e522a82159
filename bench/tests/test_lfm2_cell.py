"""The LFM2 serving cell at a CPU size, through ``bench/run.py`` with the chip
check stood in for: it runs end to end and is correct, the program's
counters of the held experts' work fit the engine's calls, and its control
reads well above the program. (``conftest.tiny_cell`` cuts only the OLMo and Forge cells, so
this file cuts this one itself.)"""

import copy

import numpy as np
import pytest

from bench import lfm2_work, peaks, run
from bench.harness import serve_lfm2
from conftest import cpu_device

CELL = "lfm2-8b-a1b.serve.batch"
TINY_CONFIG = {"hidden_size": 64, "intermediate_size": 96,
               "moe_intermediate_size": 32,
               "layer_types": ["conv", "conv", "full_attention", "conv"],
               "num_hidden_layers": 4, "num_attention_heads": 4,
               "num_key_value_heads": 2, "vocab_size": 256,
               "num_experts": 4, "num_experts_published": 8,
               "num_experts_per_tok": 2}
TINY_MIX = {"slots": 4, "kv_len": 32, "prompt_len": 4,
            "answer_len": {"min": 2, "max": 6},
            "arrivals": {"kind": "backlog", "requests": 64},
            "check": {"requests": 4, "block": 2, "min_tokens": 4,
                      "max_logit_gap": 0.3}}


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    load = run.load_cell

    def cut(name):
        cell = copy.deepcopy(load(name))
        cell["config"].update(TINY_CONFIG)
        cell["mix"].update(copy.deepcopy(TINY_MIX))
        return cell
    monkeypatch.setattr(run, "load_cell", cut)
    monkeypatch.setattr(run, "CACHE", tmp_path)
    monkeypatch.setattr(run, "_device_check", cpu_device)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    return cut


def test_cell_runs_and_is_correct(tiny):
    r = run.run_cell(CELL, 2**40 + 17, 1.0, False)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert r["checks"]["tokens_checked"]["value"] >= 4


def test_traced_run_reports_only_per_layer_metrics(tiny):
    r = run.run_cell(CELL, 3, 1.0, True)
    names = {m["name"] for m in run.load_cell(CELL)["per_layer"]}
    assert names == {"idle_share.lfm2-batch", "moe_roofline.lfm2-batch"}
    # a CPU trace has no TPU plane: both readers find nothing to read
    assert set(r["metrics"]) <= names
    assert r["correct"] is True


def test_expert_counters_bound_the_engines_calls(tiny):
    """The program's counters, summed over the window's waves: every call
    of a held-expert layer computes at most slots x top-k rows, and reads
    at most the held experts."""
    import jax
    cell = tiny(CELL)
    ctx = run.Context(cell, 5, 1.0, False, jax.devices()[0], 1)
    serve_lfm2.run(ctx)
    rec, cfg = ctx.records, cell["config"]
    calls = rec["decode_calls"] * lfm2_work.moe_layers(cfg)
    assert rec["decode_calls"] > 0
    assert 0 < rec["expert_rows"] <= (calls * TINY_MIX["slots"]
                                      * cfg["num_experts_per_tok"])
    assert 0 < rec["expert_reads"] <= calls * cfg["num_experts"]
    assert rec["expert_reads"] <= rec["expert_rows"]
    # per held-expert layer, in layer order, summing to the totals
    assert len(rec["expert_rows_by_layer"]) == lfm2_work.moe_layers(cfg)
    assert sum(rec["expert_rows_by_layer"]) == rec["expert_rows"]
    assert sum(rec["expert_reads_by_layer"]) == rec["expert_reads"]


def test_experts_work_counts_rows_and_the_weights_read():
    cfg = {"hidden_size": 2048, "moe_intermediate_size": 1792}
    flops, nbytes = lfm2_work.experts_work(cfg, rows=256, reads=8)
    assert flops == 6 * 2048 * 1792 * 256
    assert nbytes == 8 * 3 * 2048 * 1792 * 2 + 2 * 256 * 2048 * 2
    # an expert that no row chose costs nothing
    assert lfm2_work.experts_work(cfg, rows=0, reads=0) == (0, 0)
    # operands already in VMEM before the call cost it no HBM bytes
    flops_v, nbytes_v = lfm2_work.experts_work(cfg, rows=256, reads=8,
                                               mats=2, row_moves=0)
    assert flops_v == flops
    assert nbytes_v == 8 * 2 * 2048 * 1792 * 2


def test_control_reads_above_the_program(tiny):
    rs = serve_lfm2.readings(CELL, [4, 2**40 + 5], 1.0)
    for r in rs:
        assert r["control"] > 3 * r["program"]
        assert r["control"] > 0
        assert np.isfinite(r["program"])
