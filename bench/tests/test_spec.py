"""The benchmark's spec holds together: every enrolled cell finds its files
and readers by name, reports an end-to-end metric besides ``setup_s``, and
the cells still pending load by name without shadowing an enrolled one."""

import json

import pytest

from bench import run

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
PENDING = json.loads((run.BENCH / "pending.json").read_text())
ENROLLED = [w["name"] for w in SPEC["workloads"]]
KEYS = ("configs", "workloads", "end_to_end", "per_layer")


@pytest.mark.parametrize("name", ENROLLED)
def test_cell_files_and_readers_exist(name):
    cell = next(w for w in SPEC["workloads"] if w["name"] == name)
    conf = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    assert (run.ROOT / conf["file"]).is_file()
    assert (run.BENCH / "workloads" / f"{name}.json").is_file()
    loaded = run.load_cell(name)
    assert loaded["per_layer"], "a cell reports at least one per-layer metric"
    for m in loaded["per_layer"]:
        assert (run.BENCH / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("name", ENROLLED)
def test_an_end_to_end_metric_lists_the_cell(name):
    listed = [m["name"] for m in SPEC["end_to_end"]
              if name in m.get("workloads", [])]
    assert listed, f"no end-to-end metric besides setup_s lists {name}"
    assert all(m["bound"] is not None for m in SPEC["end_to_end"])


@pytest.mark.parametrize("key", KEYS)
def test_no_name_is_both_enrolled_and_pending(key):
    enrolled = {e["name"] for e in SPEC[key]}
    pending = {e["name"] for e in PENDING.get(key, [])}
    assert not enrolled & pending


def test_pending_chat_cell_still_loads_by_name():
    cell = run.load_cell("olmo-1b.serve.chat")
    assert cell["cell"]["config"] == "olmo-1b"
    assert {m["name"] for m in cell["end_to_end"]} == {
        "request_latency_p90_ms", "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "idle_share.chat", "attn_roofline.chat", "serve_mfu.chat"}
