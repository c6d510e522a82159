"""CPU rehearsal of every cell at a tiny size: the harness runs end to end
with the chip check stood in for, the last line parses, and ``correct`` is
computed from the comparison."""

import json

import numpy as np
import pytest

from bench import run

CELLS = [w["name"] for w in run.load_spec()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(tiny, name):
    r = tiny(name, seed=2**40 + 17)
    cell = run.load_cell(name)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks" and r["checks"]
    assert r["device"]["count"] == cell["cell"]["chips"]


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_per_layer_and_breakdown(tiny, name):
    r = tiny(name, seed=3, trace=True)
    cell = run.load_cell(name)
    assert set(r["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert r["correct"] is True


def test_last_line_is_the_result(tiny, capsys):
    run.main(["--workload", "olmo-1b.serve.batch", "--seed", "5",
              "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device",
            "checks"} <= set(last)
    assert err.strip().splitlines()[-1].startswith("check ")


def test_seed_fixes_the_traffic():
    from bench import traffic
    # an open-loop mix, as a Poisson cell's file gives it
    mix = dict(run.load_cell("olmo-1b.serve.batch")["mix"],
               arrivals={"kind": "poisson", "rate": 3.5})
    a = traffic.requests(mix, 2**40 + 1, 30, 50304)
    b = traffic.requests(mix, 2**40 + 1, 30, 50304)
    c = traffic.requests(mix, 7, 30, 50304)
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    # another seed: the same sizes and gaps, in another order
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in c)
    assert [r["max_new"] for r in a] != [r["max_new"] for r in c]
    # the gaps are one set, permuted: all but the first are shared
    gaps = [sorted(np.diff([r["due"] for r in x])) for x in (a, c)]
    assert len(set(np.round(gaps[0], 9)) - set(np.round(gaps[1], 9))) <= 1
