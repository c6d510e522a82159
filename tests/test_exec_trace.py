"""What the profiler's trace shows of ``run_program``: JAX's own lowering
span inside an annotation around the call, each Pallas template under its
own kernel name, and nothing registered with ``jax.monitoring``."""

import os
import pathlib
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.core import executor as ex
from repro.core.executor import run_program
from repro.ir import GraphBuilder
from repro.ir.cost import graph_flops
from repro.ir.interpreter import make_inputs, make_params
from repro.ir.schedule import KernelProgram, PallasConfig, eager_schedule
from repro.kernels.decode_attention import decode_attention
from repro.kernels.elementwise import elementwise_chain
from repro.kernels.epilogue import EpilogueOp
from repro.kernels.matmul_fused import matmul_fused, matmul_fused_naive
from repro.kernels.rmsnorm import rmsnorm

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def two_group_program() -> KernelProgram:
    """A Pallas matmul group, then an XLA group."""
    b = GraphBuilder("two_groups")
    x = b.input((64, 128), name="x")
    w = b.param((128, 128), name="w")
    mm = b.matmul(x, w, name="mm")
    g = b.done(b.gelu(mm, name="act"))
    sched = eager_schedule(g)
    for grp in sched.groups:
        if grp.root == "mm":
            grp.impl = "pallas_blockspec"
            grp.config = PallasConfig(64, 128, 128)
    return KernelProgram("two_groups", g, sched,
                         original_flops=graph_flops(g))


def host_events(trace_dir, names) -> list:
    """(name, start, end) of the host events named in ``names``, in start
    order."""
    f, = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(f))
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name in names]
    return sorted(out, key=lambda e: e[1])


def test_pallas_lowering_is_a_named_span_inside_the_call(tmp_path):
    ex._executables.clear()       # a first call, whatever ran before in this process
    p = two_group_program()
    inputs, params = make_inputs(p.graph), make_params(p.graph)
    assert [g.impl for g in p.schedule.groups] == ["pallas_blockspec", "xla"]
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.call.two_groups"):
            jax.block_until_ready(run_program(p, inputs, params))
    (_, c0, c1), = host_events(tmp_path, {"bench.call.two_groups"})
    # the span name the host-dispatch readers count lowerings by
    lowerings = host_events(tmp_path, {"lower_sharding_computation"})
    assert any(c0 <= s <= e <= c1 for _, s, e in lowerings)


_A = jax.ShapeDtypeStruct((128, 256), jnp.bfloat16)
_B = jax.ShapeDtypeStruct((256, 128), jnp.bfloat16)
_X = jax.ShapeDtypeStruct((256, 128), jnp.bfloat16)
_W = jax.ShapeDtypeStruct((128,), jnp.bfloat16)
_Q = jax.ShapeDtypeStruct((2, 8, 128), jnp.bfloat16)
_KV = jax.ShapeDtypeStruct((2, 2, 512, 128), jnp.bfloat16)
_RELU = [EpilogueOp("relu")]
KERNELS = {
    "matmul_fused_epilogue": (lambda a, b: matmul_fused(
        a, b, epilogue=_RELU, interpret=False), (_A, _B)),
    "matmul_fused_reduce": (lambda a, b: matmul_fused(
        a, b, epilogue=_RELU, reduction="sum", interpret=False), (_A, _B)),
    "matmul_fused_naive": (lambda a, b: matmul_fused_naive(
        a, b, epilogue=_RELU, interpret=False), (_A, _B)),
    "elementwise_chain": (lambda x: elementwise_chain(
        x, _RELU, interpret=False), (_X,)),
    "rmsnorm": (lambda x, w: rmsnorm(x, w, interpret=False), (_X, _W)),
    "decode_attention": (lambda q, k, v: decode_attention(
        q, k, v, interpret=False), (_Q, _KV, _KV)),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_name_reaches_the_tpu_custom_call(name):
    """Lowered for a TPU (no chip needed), each template is one
    ``tpu_custom_call`` that carries its own kernel name, which the
    device trace then shows."""
    fn, shapes = KERNELS[name]
    text = jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 1
    assert re.findall(r'kernel_name = "(\w+)"', text) == [name]


def test_run_program_registers_no_monitoring_listener():
    """In a fresh process: the ``jax.monitoring`` listener lists are the
    same before the executor is imported and after a program has run."""
    code = textwrap.dedent("""
        import jax
        from jax._src import monitoring as m

        def lists():
            return [m.get_event_listeners(), m.get_event_duration_listeners(),
                    m.get_event_time_span_listeners(),
                    m.get_scalar_listeners()]

        jax.block_until_ready(jax.numpy.ones(4) + 1)
        before = lists()
        sys.path.insert(0, TESTS)
        from test_exec_trace import two_group_program
        from repro.core.executor import run_program
        from repro.ir.interpreter import make_inputs, make_params
        p = two_group_program()
        jax.block_until_ready(run_program(p, make_inputs(p.graph),
                                          make_params(p.graph)))
        assert lists() == before, (before, lists())
        print("listeners", [len(x) for x in before])
        """)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(SRC)] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    prelude = f"import sys\nTESTS = {str(pathlib.Path(__file__).parent)!r}\n"
    r = subprocess.run([sys.executable, "-c", prelude + code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "listeners" in r.stdout
