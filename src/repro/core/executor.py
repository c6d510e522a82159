"""Trusted execution path for kernel programs.

``program_callable`` turns a :class:`KernelProgram` into a function the
verifier runs. This module — not the candidate — owns input generation,
weight seeding and dispatch (the paper's *kernel harness separation*, §VII-a):
a candidate is only a (graph, schedule) value; it cannot route execution back
to the oracle or touch the harness.

Pallas-impl groups are executed through the real kernels: compiled by Mosaic
on a TPU, in the Pallas interpreter elsewhere (``repro.kernels.platform``);
XLA-impl groups evaluate node-by-node with jnp. Mixed precision follows the
TPU pattern: external group inputs are stored/loaded in the schedule's
compute dtype, math runs in f32 (MXU: bf16 in, f32 accumulate).

``group_exec_signature`` must stay in lockstep with ``run_group``'s actual
dispatch: it is the *effective*-dispatch key of the verification fast path's
group memo and of the cross-job shared cache, so any new input that changes
what ``run_group`` computes (a template choice, a clamped block, a dtype
rule) must fold into the signature — and the batch planner's pre-executions
(:meth:`OptimizationEngine._plan_batch`) dispatch through these same
functions precisely so parent and worker derive bit-identical keys.

Dispatch is one compiled executable per program structure. ``run_program``
keys a bounded cache on everything it reads of a program (graph nodes and
outputs, each group's nodes, impl and ``group_exec_signature``, the compute
dtype and ``use_pallas``) and, on a miss, wraps its whole body in one
``jax.jit`` over ``(inputs, params)``: a later call of the same structure is
one launch, with no tracing or lowering of the Pallas kernels. So a new input
that changes what ``run_group`` computes must enter both
``group_exec_signature`` and that key (``_program_key``); one that enters
neither would let two programs share an executable.
"""

from __future__ import annotations

import collections
import threading
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.ir.graph import Graph, Node
from repro.ir.interpreter import op_impl
from repro.ir.schedule import FusionGroup, KernelProgram
from repro.kernels.epilogue import EpilogueOp
from repro.kernels.matmul_fused import matmul_fused, matmul_fused_naive
from repro.kernels.elementwise import elementwise_chain
from repro.kernels.rmsnorm import rmsnorm as rmsnorm_kernel


class ExecUnsupported(Exception):
    """A pallas impl was requested for a group with no kernel template."""


_EPILOGUE_UNARY = ("relu", "gelu", "silu", "swish", "sigmoid", "tanh", "mish",
                   "exp", "abs", "square", "neg", "softplus", "identity",
                   "dropout")
_EPILOGUE_BINARY = ("add", "sub", "mul", "div", "minimum", "maximum", "bias_add")
_EPILOGUE_SCALAR = ("scale", "add_scalar", "clamp_min", "clamp_max")
_RED_MAP = {"reduce_sum": "sum", "reduce_max": "max", "reduce_min": "min",
            "reduce_mean": "mean"}


def group_order(graph: Graph, groups: List[FusionGroup]) -> List[FusionGroup]:
    """Topological order over the group dependency DAG."""
    owner = {n: g.name for g in groups for n in g.nodes}
    deps: Dict[str, set] = {g.name: set() for g in groups}
    by_name = {g.name: g for g in groups}
    for g in groups:
        for n in g.nodes:
            for i in graph.node(n).inputs:
                o = owner.get(i)
                if o is not None and o != g.name:
                    deps[g.name].add(o)
    out, done = [], set()
    pending = list(groups)
    while pending:
        progressed = False
        for g in list(pending):
            if deps[g.name] <= done:
                out.append(g)
                done.add(g.name)
                pending.remove(g)
                progressed = True
        if not progressed:
            raise ValueError("cyclic group dependency")
    return out


# ----------------------------------------------------------------------
# template matching for pallas groups
# ----------------------------------------------------------------------

def _as_epilogue(graph: Graph, nodes: List[Node], produced: set,
                 start_value: str) -> Tuple[List[EpilogueOp], List[str]]:
    """Convert a linear elementwise chain into EpilogueOps. Returns
    (epilogue, external operand names). Raises ExecUnsupported on mismatch."""
    epilogue: List[EpilogueOp] = []
    operands: List[str] = []
    current = start_value
    for n in nodes:
        if current not in n.inputs:
            raise ExecUnsupported(f"epilogue node {n.name} does not consume the chain")
        others = [i for i in n.inputs if i != current]
        if n.op in _EPILOGUE_UNARY:
            if others:
                raise ExecUnsupported(f"unary {n.name} with extra inputs")
            if n.op not in ("identity", "dropout"):
                epilogue.append(EpilogueOp(n.op))
        elif n.op in _EPILOGUE_SCALAR:
            epilogue.append(EpilogueOp(n.op, value=float(n.attrs["value"])))
        elif n.op in _EPILOGUE_BINARY:
            if len(others) != 1:
                raise ExecUnsupported(f"binary {n.name} needs exactly one operand")
            src = graph.node(others[0])
            if src.op == "const":
                epilogue.append(EpilogueOp(n.op, value=float(src.attrs["value"])))
            elif others[0] in produced:
                raise ExecUnsupported(
                    f"binary {n.name} consumes an in-group intermediate")
            else:
                # operand order matters for sub/div: chain value must be lhs
                if n.inputs[0] != current and n.op in ("sub", "div"):
                    raise ExecUnsupported(f"{n.name}: chain value is rhs of {n.op}")
                epilogue.append(EpilogueOp(n.op, operand=others[0]))
                operands.append(others[0])
        else:
            raise ExecUnsupported(f"op {n.op} not fusable as epilogue")
        current = n.name
    return epilogue, operands


def _run_pallas_group(graph: Graph, group: FusionGroup, env: Dict[str, jnp.ndarray],
                      compute_dtype) -> Dict[str, jnp.ndarray]:
    nodes = [graph.node(n) for n in group.nodes]
    produced = set(group.nodes)
    cfg = group.config
    naive = group.impl == "pallas_naive"

    def load(name: str) -> jnp.ndarray:
        return env[name].astype(compute_dtype)

    # template 1: single rmsnorm
    if len(nodes) == 1 and nodes[0].op == "rmsnorm":
        n = nodes[0]
        x = load(n.inputs[0])
        w = env[n.inputs[1]] if len(n.inputs) > 1 else jnp.ones(x.shape[-1], x.dtype)
        lead, d = x.shape[:-1], x.shape[-1]
        out = rmsnorm_kernel(x.reshape(-1, d), w,
                             eps=n.attrs.get("eps", 1e-6)).reshape(*lead, d)
        return {n.name: out}

    # template 2: matmul (+ epilogue chain) (+ terminal row reduction)
    if nodes[0].op == "matmul" and len(nodes[0].shape) == 2:
        mm = nodes[0]
        chain = nodes[1:]
        reduction = None
        if chain and chain[-1].op in _RED_MAP:
            red = chain[-1]
            axes = tuple(ax % 2 for ax in red.attrs.get("axes", ()))
            if axes != (1,) or red.attrs.get("keepdims", False):
                raise ExecUnsupported("only row (axis=1) reductions fuse")
            reduction = _RED_MAP[red.op]
            chain = chain[:-1]
        epilogue, op_names = _as_epilogue(graph, chain, produced, mm.name)
        a = load(mm.inputs[0])
        b = load(mm.inputs[1])
        if mm.attrs.get("transpose_a"):
            a = a.T
        if mm.attrs.get("transpose_b"):
            b = b.T  # packed or not: numerics identical, cost model differs
        operands = {s: env[s].astype(compute_dtype) for s in op_names}
        m, k = a.shape
        n_ = b.shape[1]
        if naive:
            bm = min(cfg.block_m if cfg else 128, m)
            bn = min(cfg.block_n if cfg else 128, n_)
            bk = min(cfg.block_k if cfg else 128, k)
            out = matmul_fused_naive(a, b, block_m=bm, block_n=bn, block_k=bk,
                                     epilogue=epilogue, operands=operands,
                                     reduction=reduction, out_dtype=compute_dtype)
        else:
            c = cfg or type("C", (), {})()
            out = matmul_fused(
                a, b,
                block_m=min(getattr(c, "block_m", 128), m),
                block_n=min(getattr(c, "block_n", 128), n_),
                block_k=min(getattr(c, "block_k", 128), k),
                group_m=getattr(c, "group_m", 1),
                num_stages=getattr(c, "num_stages", 2),
                epilogue=epilogue, operands=operands, reduction=reduction,
                out_dtype=compute_dtype)
        last = group.nodes[-1]
        want_shape = graph.node(last).shape
        return {last: out.reshape(want_shape)}

    # template 3: pure elementwise chain
    if all(n.is_elementwise() for n in nodes):
        x_name = nodes[0].inputs[0]
        epilogue, op_names = _as_epilogue(graph, nodes, produced, x_name)
        x = load(x_name)
        lead, ccol = x.shape[:-1], x.shape[-1]
        operands = {s: env[s].astype(compute_dtype).reshape(-1, env[s].shape[-1])
                    if env[s].ndim == x.ndim else env[s].astype(compute_dtype)
                    for s in op_names}
        out = elementwise_chain(x.reshape(-1, ccol), epilogue, operands=operands,
                                out_dtype=compute_dtype)
        last = group.nodes[-1]
        return {last: out.reshape(graph.node(last).shape)}

    raise ExecUnsupported(
        f"group {group.name} ({[n.op for n in nodes]}) has no pallas template")


def _run_xla_group(graph: Graph, group: FusionGroup, env: Dict[str, jnp.ndarray],
                   compute_dtype) -> Dict[str, jnp.ndarray]:
    produced: Dict[str, jnp.ndarray] = {}

    def val(name: str) -> jnp.ndarray:
        if name in produced:
            return produced[name]
        v = env[name]
        if jnp.issubdtype(v.dtype, jnp.floating):
            # storage dtype at group boundary, f32 math inside
            return v.astype(compute_dtype).astype(jnp.float32)
        return v

    for name in group.nodes:
        n = graph.node(name)
        args = [val(i) for i in n.inputs]
        produced[name] = op_impl(n.op, n.attrs)(*args)
    # external results stored in compute dtype
    return {k: v.astype(compute_dtype) if jnp.issubdtype(v.dtype, jnp.floating) else v
            for k, v in produced.items()}


# ----------------------------------------------------------------------
def run_group(graph: Graph, group: FusionGroup, env: Dict[str, jnp.ndarray],
              compute_dtype, use_pallas: bool = True) -> Dict[str, jnp.ndarray]:
    """Execute one fusion group against ``env`` — the single dispatch point
    shared by :func:`run_program` and the memoizing fast path
    (``repro.core.verify_cache``). Returns the name->array bindings the
    group contributes (pallas templates emit only their final node)."""
    if group.impl.startswith("pallas") and use_pallas:
        return _run_pallas_group(graph, group, env, compute_dtype)
    return _run_xla_group(graph, group, env, compute_dtype)


def group_exec_signature(graph: Graph, group: FusionGroup,
                         use_pallas: bool = True) -> tuple:
    """The *effective* dispatch parameters :func:`run_group` would hand the
    kernel templates — everything config-derived that can change the
    computed values, and nothing more. This is the config half of the fast
    path's group cache key (node ops/attrs/shapes are keyed separately), and
    it deliberately collapses distinct configs with identical effect: the
    templates clamp blocks to the operand dims, so on small ci shapes a
    (512,512,512) and a (1024,1024,1024) candidate execute identically and
    may share one cached run.

    MUST stay in lockstep with the template dispatch above: a template that
    starts reading a new config field has to fold it in here, which is why
    this lives in the executor and not next to the cache."""
    nodes = [graph.node(n) for n in group.nodes]
    if not (group.impl.startswith("pallas") and use_pallas):
        # the XLA runner reads only ops/attrs (already in the node payload)
        return ("xla",)
    cfg = group.config
    if len(nodes) == 1 and nodes[0].op == "rmsnorm":
        return ("rmsnorm",)                      # template 1 ignores cfg
    if nodes[0].op == "matmul" and len(nodes[0].shape) == 2:
        mm = nodes[0]
        a_shape = graph.node(mm.inputs[0]).shape
        b_shape = graph.node(mm.inputs[1]).shape
        m, k = ((a_shape[1], a_shape[0]) if mm.attrs.get("transpose_a")
                else (a_shape[0], a_shape[1]))
        n_ = b_shape[0] if mm.attrs.get("transpose_b") else b_shape[1]
        if group.impl == "pallas_naive":
            return ("matmul_naive",
                    min(cfg.block_m if cfg else 128, m),
                    min(cfg.block_n if cfg else 128, n_),
                    min(cfg.block_k if cfg else 128, k))
        return ("matmul",
                min(getattr(cfg, "block_m", 128) if cfg else 128, m),
                min(getattr(cfg, "block_n", 128) if cfg else 128, n_),
                min(getattr(cfg, "block_k", 128) if cfg else 128, k),
                getattr(cfg, "group_m", 1) if cfg else 1,
                getattr(cfg, "num_stages", 2) if cfg else 2)
    if all(n.is_elementwise() for n in nodes):
        return ("elementwise",)                  # template 3 ignores cfg
    # unknown shape (run_group would raise ExecUnsupported): key on the full
    # raw group description so nothing can alias
    return ("raw", group.impl,
            tuple(sorted(cfg.to_dict().items())) if cfg else None,
            tuple(sorted(group.operand_layouts.items())), group.prefetch)


class _ExecutableCache:
    """Jitted programs by structural key, the least recently used dropped
    first beyond ``cap``. Forge's threads share it, hence the lock."""

    def __init__(self, cap: int):
        self.cap = cap
        self.hits = self.misses = 0
        self._entries: "collections.OrderedDict[tuple, tuple]" = \
            collections.OrderedDict()
        self._lock = threading.Lock()

    def get(self, key: tuple):
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.misses += 1
            else:
                self._entries.move_to_end(key)
                self.hits += 1
            return entry

    def put(self, key: tuple, entry: tuple):
        with self._lock:
            self._entries[key] = entry
            while len(self._entries) > self.cap:
                self._entries.popitem(last=False)

    def clear(self):
        with self._lock:
            self._entries.clear()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "size": len(self._entries)}


# Forge verification runs many one-shot programs through the same cache
_executables = _ExecutableCache(cap=256)


def _program_key(program: KernelProgram, use_pallas: bool) -> tuple:
    """Everything :func:`run_program` reads of ``program``. Attributes enter
    by ``repr``, which tells ``-0.0`` from ``0.0`` and ``1`` from ``1.0``."""
    graph, sched = program.graph, program.schedule
    return (tuple((n.name, n.op, tuple(n.inputs), repr(n.attrs),
                   tuple(n.shape), n.dtype) for n in graph.nodes.values()),
            tuple(graph.outputs),
            tuple((g.name, tuple(g.nodes), g.impl,
                   group_exec_signature(graph, g, use_pallas))
                  for g in sched.groups),
            sched.compute_dtype, use_pallas)


def _compile_program(program: KernelProgram, use_pallas: bool):
    """A jitted function of ``(inputs, params)`` returning the outputs in
    ``graph.outputs`` order, traced from a copy of ``program`` so that a
    later in-place edit of it cannot reach a retrace."""
    graph, sched = program.graph.copy(), program.schedule.copy()
    compute_dtype = jnp.dtype(sched.compute_dtype)

    def program_body(inputs, params):
        env: Dict[str, jnp.ndarray] = {}
        for n in graph.toposorted():
            if n.op == "input":
                env[n.name] = inputs[n.name]
            elif n.op == "param":
                env[n.name] = params[n.name]
            elif n.op == "const":
                env[n.name] = jnp.asarray(n.attrs["value"], jnp.dtype(n.dtype))
        for g in group_order(graph, sched.groups):
            env.update(run_group(graph, g, env, compute_dtype,
                                 use_pallas=use_pallas))
        return tuple(env[o].astype(jnp.float32) for o in graph.outputs)

    return jax.jit(program_body), tuple(graph.outputs)


def program_cache_stats() -> Dict[str, int]:
    """Hits, misses and size of :func:`run_program`'s executable cache."""
    return _executables.stats()


def run_program(program: KernelProgram,
                inputs: Dict[str, jnp.ndarray],
                params: Dict[str, jnp.ndarray],
                use_pallas: bool = True) -> Dict[str, jnp.ndarray]:
    key = _program_key(program, use_pallas)
    entry = _executables.get(key)
    fn, outputs = entry or _compile_program(program, use_pallas)
    out = fn(inputs, params)     # a trace that raises stores nothing
    if entry is None:
        _executables.put(key, (fn, outputs))
    return dict(zip(outputs, out))


def program_callable(program: KernelProgram, params: Dict[str, jnp.ndarray],
                     use_pallas: bool = True):
    def fn(inputs: Dict[str, jnp.ndarray]):
        return run_program(program, inputs, params, use_pallas=use_pallas)
    return fn
