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


_INDEX_RE = re.compile(r"/\*index=\d+\*/")      # in long tuple types
_HEADER_RE = re.compile(r"\s*(?:ENTRY\s+)?%([\w.-]+)\s+\(")
_CHAIN_RE = re.compile(r'chain_id="(\d+)"')
_RS_CALL_RE = re.compile(r"calls=%(all-reduce-scatter[\w.-]*)")


def _result_bytes(rtype: str) -> int:
    nbytes = 0
    for dt, dims in _SHAPE_RE.findall(rtype):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _dtype_bytes(dt)
    return nbytes


def collective_bytes(hlo_text: str, while_mult: int = 1) -> dict:
    """Per-device *wire* bytes per collective kind, from partitioned HLO.

    Result-type bytes R, group size G, ring algorithms:
      all-reduce: 2(G-1)/G x R   all-gather: (G-1)/G x R_out
      reduce-scatter: (G-1) x R_out   all-to-all: (G-1)/G x R
      collective-permute: R
    A fusion that calls an ``%all-reduce-scatter`` computation (how the
    TPU compiler fuses an all-reduce with the slice each chip keeps) is
    one reduce-scatter of (G-1)/G x its all-reduce's R; that inner
    all-reduce is not counted again.  A collective the compiler splits
    over several asynchronous fusions carries one ``chain_id`` in each,
    and counts once.  Ops inside while bodies (scan over layers) are
    multiplied by ``while_mult`` (the scan trip count) — the body appears
    once in text but executes every step.
    """
    out = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    comp, scatters, lines = "", {}, []
    for line in _INDEX_RE.sub("", hlo_text).splitlines():
        h = _HEADER_RE.match(line)
        if h:
            comp = h.group(1)
            continue
        m = _OP_RE.search(line)
        if comp.startswith("all-reduce-scatter"):
            if m and m.group("kind") == "all-reduce":
                scatters[comp] = (_result_bytes(m.group("rtype")),
                                  _group_size(line))
            continue
        lines.append((line, m))
    chains = set()
    for line, m in lines:
        mult = while_mult if "/while/" in line or "while" in line.split(
            "metadata", 1)[-1] else 1
        call = _RS_CALL_RE.search(line)
        if call and call.group(1) in scatters:
            nbytes, G = scatters[call.group(1)]
            out["reduce-scatter"] += nbytes * (G - 1) / G * mult
            continue
        if not m or m.group("start") == "-done":
            continue
        kind = m.group("kind")
        chain = _CHAIN_RE.search(line)
        if chain:
            if (kind, chain.group(1)) in chains:
                continue
            chains.add((kind, chain.group(1)))
        nbytes = _result_bytes(m.group("rtype")) \
            or _result_bytes(line.split("(")[0])
        G = _group_size(line)
        ring = {"all-reduce": 2.0 * (G - 1) / G,
                "all-gather": (G - 1) / G,
                "reduce-scatter": float(G - 1),
                "all-to-all": (G - 1) / G,
                "collective-permute": 1.0}[kind]
        out[kind] += nbytes * ring * mult
    return {k: int(v) for k, v in out.items()}


def layer_trips(cfg, microbatches: int = 1) -> int:
    """How often one step runs its layer scan's body: the ``while_mult``
    of ``collective_bytes``."""
    period = cfg.attn_every or 1
    n_steps = (cfg.n_layers // period) if cfg.scan_layers else 1
    return max(1, n_steps * max(1, microbatches))
