"""Work counts of LFM2-8B-A1B serving at one chip's expert share, from
shapes and the program's counters of the held experts' work."""

from __future__ import annotations


def moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def experts_work(cfg: dict, rows: int, reads: int, nbytes: int = 2,
                 mats: int = 3, row_moves: int = 2) -> tuple:
    """(flops, bytes) of the held experts' calls that computed ``rows``
    token-expert pairs in all, with ``reads`` (call, expert) pairs that had
    rows: 6 d f flops a pair (three products of d x f); of each such
    expert's three matrices, the ``mats`` the calls read from HBM, once a
    call; and each pair's row on the ``row_moves`` of its ways in and out
    that go through HBM. An operand that XLA has already copied into VMEM
    before a call is read by that copy, not by the call."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (6.0 * d * f * rows,
            (reads * mats * d * f + row_moves * rows * d) * nbytes)
