"""What the residual streams of a manifold-constrained hyper-connection
model (``model_type: xing4_0``) must move, from the configuration file's
dict alone: a change to the program cannot move it.

A sublayer (attention, feed-forward: two a layer) of a row that holds a
token reads the row's ``n`` streams once and writes them once, writes the
sublayer's input ``h`` and reads its output ``y``: ``(2n + 2) * C`` elements
of the model's dtype; and it reads its float32 ``phi [n*C, n*n + 2n]`` once
for all rows.  ``b``, ``alpha``, the ``n*n + 2n`` numbers of the maps a row
and the entry and exit of the streams are left out: a floor.
"""

from __future__ import annotations

from typing import Any, Dict


def sublayers(cfg: Dict[str, Any]) -> int:
    return 2 * cfg["num_hidden_layers"]


def phi_bytes(cfg: Dict[str, Any]) -> int:
    """One sublayer's ``phi``, float32 (1.38 MB at the published widths)."""
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * (n * n + 2 * n) * 4


def row_bytes(cfg: Dict[str, Any], bytes_el: int = 2) -> int:
    """One row through one sublayer (71.7 KB in bfloat16)."""
    return (2 * cfg["hc_mult"] + 2) * cfg["hidden_size"] * bytes_el


def stream_bytes(cfg: Dict[str, Any], rows: float,
                 bytes_el: int = 2) -> float:
    """One step over ``rows`` rows that hold a token, all sublayers."""
    return sublayers(cfg) * (rows * row_bytes(cfg, bytes_el)
                             + phi_bytes(cfg))
