"""Per-chip wire bytes of the collectives in a partitioned program, read
from its compiled HLO text: what ``train.dispatch`` carries as
``collective_bytes`` on a mesh."""

from __future__ import annotations

import re


def _dtype_bytes(name: str) -> float:
    return {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
            "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8, "c64": 8, "c128": 16}.get(name, 4)


_SHAPE_RE = re.compile(r"(pred|[us]\d+|bf16|f16|f32|f64|c64|c128)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?P<rtype>[^=]*?)\s*"
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start|-done)?\(")


def _group_size(line: str, default: int = 2) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)     # iota v2
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)      # explicit
    if m:
        return len(m.group(1).split(","))
    return default


def collective_bytes(hlo_text: str, while_mult: int = 1) -> dict:
    """Per-device *wire* bytes per collective kind, from partitioned HLO.

    Result-type bytes R, group size G, ring algorithms:
      all-reduce: 2(G-1)/G x R   all-gather: (G-1)/G x R_out
      reduce-scatter: (G-1) x R_out   all-to-all: (G-1)/G x R
      collective-permute: R
    Ops inside while bodies (scan over layers) are multiplied by
    ``while_mult`` (the scan trip count) — the body appears once in text
    but executes every step.
    """
    out = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m or m.group("start") == "-done":
            continue
        kind = m.group("kind")
        shapes = _SHAPE_RE.findall(m.group("rtype"))
        if not shapes:
            shapes = _SHAPE_RE.findall(line.split("(")[0])
        nbytes = 0
        for dt, dims in shapes:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _dtype_bytes(dt)
        G = _group_size(line)
        ring = {"all-reduce": 2.0 * (G - 1) / G,
                "all-gather": (G - 1) / G,
                "reduce-scatter": float(G - 1),
                "all-to-all": (G - 1) / G,
                "collective-permute": 1.0}[kind]
        mult = while_mult if "/while/" in line or "while" in line.split(
            "metadata", 1)[-1] else 1
        out[kind] += nbytes * ring * mult
    return {k: int(v) for k, v in out.items()}


def layer_trips(cfg, microbatches: int = 1) -> int:
    """How often one step runs its layer scan's body: the ``while_mult``
    of ``collective_bytes``."""
    period = cfg.attn_every or 1
    n_steps = (cfg.n_layers // period) if cfg.scan_layers else 1
    return max(1, n_steps * max(1, microbatches))
