"""``run_program`` compiles each program structure once and reuses it: a
second call is a hit with no lowering, a structural difference is a miss,
the cached result is the per-group eager one, and a program that fails to
trace stores nothing."""

import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.aibench.spec import load_specs
from repro.aibench.suite import build_program
from repro.core import executor as ex
from repro.core.executor import (ExecUnsupported, group_order,
                                 program_cache_stats, run_group, run_program)
from repro.ir import GraphBuilder
from repro.ir.cost import graph_flops
from repro.ir.interpreter import make_inputs, make_params
from repro.ir.schedule import KernelProgram, PallasConfig, eager_schedule

from test_exec_trace import host_events, two_group_program


@pytest.fixture(autouse=True)
def empty_cache():
    """No test depends on what an earlier one, in this file or another that
    shares the worker process, left in the cache."""
    ex._executables.clear()


def eager(program, inputs, params, use_pallas=True):
    """The executor's per-group eager dispatch, one ``run_group`` call at a
    time."""
    graph, sched = program.graph, program.schedule
    cd = jnp.dtype(sched.compute_dtype)
    env = dict(inputs, **params)
    for n in graph.nodes.values():
        if n.op == "const":
            env[n.name] = jnp.asarray(n.attrs["value"], jnp.dtype(n.dtype))
    for g in group_order(graph, sched.groups):
        env.update(run_group(graph, g, env, cd, use_pallas=use_pallas))
    return {o: env[o].astype(jnp.float32) for o in graph.outputs}


def stats_delta(before):
    now = program_cache_stats()
    return {k: now[k] - before[k] for k in ("hits", "misses", "size")}


def test_second_call_is_a_hit_with_no_lowering(tmp_path):
    p = two_group_program()
    inputs, params = make_inputs(p.graph), make_params(p.graph)
    first = jax.block_until_ready(run_program(p, inputs, params))
    before = program_cache_stats()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.call.two_groups"):
            second = jax.block_until_ready(run_program(p, inputs, params))
    assert stats_delta(before) == {"hits": 1, "misses": 0, "size": 0}
    (_, c0, c1), = host_events(tmp_path, {"bench.call.two_groups"})
    inner = [e for e in host_events(tmp_path, {
        "lower_sharding_computation", "backend_compile_and_load"})
        if c0 <= e[1] <= c1]
    assert inner == []
    np.testing.assert_array_equal(first["act"], second["act"])


def scaled_matmul() -> KernelProgram:
    """One Pallas group: x @ w, then a scalar epilogue."""
    b = GraphBuilder("scaled_matmul")
    x = b.input((64, 128), name="x")
    w = b.param((128, 128), name="w")
    g = b.done(b.scale(b.matmul(x, w, name="mm"), value=2.0, name="sc"))
    sched = eager_schedule(g)
    sched.groups = [sched.groups[0]]
    sched.groups[0].nodes = ["mm", "sc"]
    sched.groups[0].impl = "pallas_blockspec"
    sched.groups[0].config = PallasConfig(64, 128, 128)
    p = KernelProgram("scaled_matmul", g, sched, original_flops=graph_flops(g))
    p.validate()
    return p


def _block(p):
    p.schedule.groups[0].config.block_m = 32


def _scalar(p):
    p.graph.node("sc").attrs["value"] = 3.0


def _transpose_b(p):
    p.graph.node("mm").attrs["transpose_b"] = True


def _compute_dtype(p):
    p.schedule.compute_dtype = "bfloat16"


EDITS = {"block_size": _block, "epilogue_scalar": _scalar,
         "transpose_b": _transpose_b, "compute_dtype": _compute_dtype,
         "use_pallas": None}


@pytest.mark.parametrize("edit", sorted(EDITS))
def test_program_edited_in_place_misses(edit):
    """Proposers edit programs in place: the same object, edited, must not
    reuse the executable of what it was."""
    p = scaled_matmul()
    inputs, params = make_inputs(p.graph), make_params(p.graph)
    base = run_program(p, inputs, params)["sc"]
    np.testing.assert_allclose(base, eager(p, inputs, params)["sc"],
                               rtol=1e-5, atol=1e-5)
    use_pallas = edit != "use_pallas"
    if EDITS[edit] is not None:
        EDITS[edit](p)
    before = program_cache_stats()
    got = run_program(p, inputs, params, use_pallas=use_pallas)["sc"]
    assert stats_delta(before) == {"hits": 0, "misses": 1, "size": 1}
    tol = 2e-2 if edit == "compute_dtype" else 1e-5
    np.testing.assert_allclose(
        got, eager(p, inputs, params, use_pallas=use_pallas)["sc"],
        rtol=tol, atol=tol)
    if edit in ("epilogue_scalar", "transpose_b"):
        assert not np.allclose(got, base, rtol=1e-3, atol=1e-3)


def test_retrace_after_an_in_place_edit_keeps_the_cached_structure():
    """A hit with inputs of another dtype retraces the stored function. It
    must trace the structure it was stored under, not what the program it
    was built from has been edited into since."""
    p = scaled_matmul()
    inputs, params = make_inputs(p.graph), make_params(p.graph)
    run_program(p, inputs, params)
    _scalar(p)
    same = scaled_matmul()
    half = {k: v.astype(jnp.bfloat16) for k, v in inputs.items()}
    before = program_cache_stats()
    got = run_program(same, half, params)["sc"]
    assert stats_delta(before) == {"hits": 1, "misses": 0, "size": 0}
    np.testing.assert_allclose(got, eager(same, half, params)["sc"],
                               rtol=1e-5, atol=1e-5)


L2_SPECS = sorted(s.name for s in load_specs()
                  if s.family in ("gemm", "matmul"))


@pytest.mark.parametrize("name", L2_SPECS)
def test_cached_output_is_the_per_group_eager_one(name):
    spec = next(s for s in load_specs() if s.name == name)
    p = build_program(spec.builder, spec.dims("ci"), "naive")
    inputs, params = make_inputs(p.graph), make_params(p.graph)
    want = eager(p, inputs, params)
    got = run_program(p, inputs, params)
    again = run_program(p, inputs, params)
    assert list(got) == list(want) == list(again) == p.graph.outputs
    for o in want:
        np.testing.assert_allclose(got[o], want[o], rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(again[o], got[o])


def _no_template(p):
    """A Pallas impl on a group no template fits."""
    p.schedule.groups[0].impl = "pallas_blockspec"
    p.schedule.groups[0].nodes = ["mm"]
    p.schedule.groups.append(
        type(p.schedule.groups[0])(name="g_red", nodes=["sc"], root="sc",
                                   impl="pallas_blockspec"))
    p.graph.node("sc").op = "reduce_sum"
    p.graph.node("sc").attrs = {"axes": (0,)}


def _ragged_naive(p):
    """The naive kernel refuses a block that does not divide its dims."""
    p.schedule.groups[0].impl = "pallas_naive"
    p.schedule.groups[0].config = PallasConfig(48, 128, 128)


@pytest.mark.parametrize("edit,error", [(_no_template, ExecUnsupported),
                                        (_ragged_naive, ValueError)],
                         ids=["no_template", "ragged_naive"])
def test_failed_trace_stores_nothing(edit, error):
    p = scaled_matmul()
    edit(p)
    inputs, params = make_inputs(p.graph), make_params(p.graph)
    before = program_cache_stats()
    for _ in range(2):
        with pytest.raises(error):
            run_program(p, inputs, params)
    assert stats_delta(before) == {"hits": 0, "misses": 2, "size": 0}


def test_cache_is_bounded_least_recently_used_first(monkeypatch):
    monkeypatch.setattr(ex._executables, "cap", 2)
    progs = []
    for value in (1.0, 2.0, 3.0):
        p = scaled_matmul()
        p.graph.node("sc").attrs["value"] = value
        progs.append(p)
    inputs, params = make_inputs(progs[0].graph), make_params(progs[0].graph)
    run_program(progs[0], inputs, params, use_pallas=False)
    run_program(progs[1], inputs, params, use_pallas=False)
    run_program(progs[0], inputs, params, use_pallas=False)   # 0 is newest
    run_program(progs[2], inputs, params, use_pallas=False)   # evicts 1
    assert program_cache_stats()["size"] == 2
    before = program_cache_stats()
    run_program(progs[0], inputs, params, use_pallas=False)
    run_program(progs[1], inputs, params, use_pallas=False)
    assert stats_delta(before) == {"hits": 1, "misses": 1, "size": 0}


def test_threads_count_every_call_once(monkeypatch):
    """Forge's threads share the cache. Under frequent thread switches and
    evictions, every call counts once, as a hit or a miss, and gets its own
    program's answer."""
    monkeypatch.setattr(ex._executables, "cap", 2)
    progs = []
    for value in (1.0, 2.0, 3.0):
        p = scaled_matmul()
        p.graph.node("sc").attrs["value"] = value
        progs.append(p)
    inputs, params = make_inputs(progs[0].graph), make_params(progs[0].graph)
    want = [eager(p, inputs, params, use_pallas=False)["sc"] for p in progs]
    workers, calls, wrong = 12, 8, []

    def work(i):
        for j in range(calls):
            k = (i + j) % len(progs)
            try:
                got = run_program(progs[k], inputs, params, use_pallas=False)
                if not np.allclose(got["sc"], want[k], rtol=1e-5, atol=1e-5):
                    wrong.append((i, j, "value"))
            except Exception as e:  # noqa: BLE001 — reported below
                wrong.append((i, j, repr(e)))

    before = program_cache_stats()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []
    d = stats_delta(before)
    assert d["hits"] + d["misses"] == workers * calls
    assert program_cache_stats()["size"] <= 2
