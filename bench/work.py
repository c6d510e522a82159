"""Work counts from shapes: the operations and bytes a problem or a request
needs, whatever the program does to compute it.
"""

from __future__ import annotations

import ast
import operator

_BINOPS = {ast.Add: operator.add, ast.Sub: operator.sub,
           ast.Mult: operator.mul, ast.Div: operator.truediv,
           ast.Pow: operator.pow, ast.FloorDiv: operator.floordiv}


def formula(expr: str, env: dict) -> float:
    """Evaluate an arithmetic formula over dimension names: numbers, names,
    + - * / ** // and unary minus, nothing else."""
    def ev(node):
        if isinstance(node, ast.Expression):
            return ev(node.body)
        if isinstance(node, ast.Constant) and isinstance(node.value,
                                                         (int, float)):
            return node.value
        if isinstance(node, ast.Name):
            return env[node.id]
        if isinstance(node, ast.BinOp) and type(node.op) in _BINOPS:
            return _BINOPS[type(node.op)](ev(node.left), ev(node.right))
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return -ev(node.operand)
        raise ValueError(f"disallowed syntax in {expr!r}")
    return float(ev(ast.parse(expr, mode="eval")))


def l2_work(problem: dict, dims: dict) -> tuple:
    """(flops, bytes) one call of an L2 problem needs."""
    return formula(problem["flops"], dims), formula(problem["bytes"], dims)


# ----------------------------------------------------------------------
# OLMo-style decoder
# ----------------------------------------------------------------------

def token_matmul_flops(cfg: dict) -> float:
    """Matrix-product flops of one token through every layer (projections
    and MLP), without attention scores and without the unembedding."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_layer = d * hd + 2 * d * kvd + hd * d + 3 * d * f
    return 2.0 * cfg["num_hidden_layers"] * per_layer


def unembed_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def _attn_pair_flops(cfg: dict) -> float:
    """Flops of one query against one key in every layer: scores and the
    weighted sum of values."""
    return 4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * cfg["head_dim"]


def _pairs(prompt: int, generated: int) -> tuple:
    """Query-key pairs of the causal prompt pass, and of the generated
    tokens that are fed back (all but the last)."""
    fed = max(generated - 1, 0)
    return prompt * (prompt + 1) / 2.0, fed * prompt + fed * (fed + 1) / 2.0


def request_flops(cfg: dict, prompt: int, generated: int) -> float:
    """Flops a served request needs: every prompt token and every fed-back
    generated token through the layers, attention over each one's context,
    and one unembedding per generated token."""
    fed = max(generated - 1, 0)
    p_pairs, g_pairs = _pairs(prompt, generated)
    return ((prompt + fed) * token_matmul_flops(cfg)
            + (p_pairs + g_pairs) * _attn_pair_flops(cfg)
            + generated * unembed_flops(cfg))


def request_attention_work(cfg: dict, prompt: int, generated: int,
                           kv_bytes: int = 2) -> tuple:
    """(flops, bytes) of attention a served request needs: causal attention
    over the prompt with one read of its Q, K and V, and for each fed-back
    generated token one read of the keys and values of its context."""
    nl, dh = cfg["num_hidden_layers"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    p_pairs, g_pairs = _pairs(prompt, generated)
    flops = (p_pairs + g_pairs) * _attn_pair_flops(cfg)
    prompt_bytes = prompt * nl * (h + 2 * hkv) * dh * kv_bytes
    fed = max(generated - 1, 0)
    ctx = fed * prompt + fed * (fed + 1) / 2.0
    decode_bytes = ctx * nl * 2 * hkv * dh * kv_bytes
    return flops, prompt_bytes + decode_bytes
