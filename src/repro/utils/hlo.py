"""HLO text analysis: collective-communication byte accounting for the roofline.

``cost_analysis()`` reports FLOPs/bytes but NOT collective traffic, so we parse the
SPMD-partitioned module text.  For every ``all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute`` op we compute the PER-DEVICE OPERAND bytes, deriving
the operand size from the printed OUTPUT type signature and the op semantics:

    all-reduce / all-to-all / collective-permute : operand = output
    all-gather                                   : operand = output / group_size
    reduce-scatter                               : operand = output * group_size

(group size parsed from ``replica_groups``; ``-start`` counted once, ``-done``
skipped; an async ``collective-permute-start`` prints (operand, output, contexts)
and counts its operand).  Totals are per-device, matching cost_analysis' per-device convention; the
spec's total-bytes / (chips x link_bw) equals our per-device bytes / link_bw.
"""
from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# a tuple signature; a TPU layout inside it holds parentheses of its own
# (``f32[20,1]{0,1:T(1,128)S(1)}``)
_TUPLE = r"\((?:[^()]|\([^()]*\))*\)"
_COLL_RE = re.compile(
    r"=\s*(" + _TUPLE + r"|\w+\[[\d,]*\][^\s]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _sig_bytes(sig: str) -> int:
    """Bytes of one type signature, possibly a tuple '(bf16[2,3], f32[4])'."""
    total = 0
    for m in _SHAPE_RE.finditer(sig):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:  # iota format [num_groups,group_size]
        return max(1, int(m.group(2)))
    m = _GROUPS_RE.search(line)
    if m:
        return max(1, len(m.group(1).split(",")))
    return 1


def _op_bytes(sig: str, kind: str, start: bool, g: int) -> float:
    """Per-device operand bytes of one collective from its printed output
    signature (``start``: the async ``-start`` op's tuple)."""
    out_bytes = _sig_bytes(sig)
    if kind == "all-gather":
        # start-op tuple prints (operand, output): take largest as output
        return out_bytes / (1 + 1.0 / g) / g if start else out_bytes / g
    if kind == "reduce-scatter":
        return out_bytes * g
    if kind == "all-reduce" and start:
        return out_bytes / 2  # start tuple prints (operand, output)
    if kind == "collective-permute" and start:
        # (operand, output, context...): the operand is the first shape
        return _sig_bytes(_SHAPE_RE.search(sig).group(0))
    return out_bytes


def collective_bytes(hlo_text: str) -> dict:
    """Per-device operand bytes by collective kind (+ op counts)."""
    by_kind: dict[str, float] = defaultdict(float)
    counts: dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        sig, kind = m.group(1), m.group(2)
        op_bytes = _op_bytes(sig, kind, bool(m.group(3)), _group_size(line))
        by_kind[kind] += op_bytes
        counts[kind] += 1
    return {
        "bytes_by_kind": dict(by_kind),
        "counts": dict(counts),
        "total_bytes": float(sum(by_kind.values())),
    }


def named_scope_counts(hlo_text: str, prefix: str = "dd-") -> dict[str, int]:
    """Ops attributed to each ``jax.named_scope`` starting with ``prefix``.

    Scope names appear as path components of the ``op_name`` metadata
    (``jit(f)/.../dd-comm-halo/...``); counting ops per scope lets tests and
    the comp/comm splitter assert the annotation scheme holds (e.g. every
    collective-permute sits under ``dd-comm-halo``).  An op nested under two
    matching scopes counts toward each (scopes are a hierarchy, not a
    partition)."""
    counts: dict[str, int] = defaultdict(int)
    pat = re.compile(r'op_name="([^"]+)"')
    for m in pat.finditer(hlo_text):
        for part in m.group(1).split("/"):
            if part.startswith(prefix):
                counts[part] += 1
    return dict(counts)


def op_histogram(hlo_text: str, top: int = 25) -> list[tuple[str, int]]:
    """Crude opcode histogram of the entry/partitioned module (dup-spotting)."""
    ops = defaultdict(int)
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*(?:\([^)]*\)|\w+\[[^\]]*\]\S*)\s+([a-z0-9-]+)\(", line)
        if m:
            ops[m.group(1)] += 1
    return sorted(ops.items(), key=lambda kv: -kv[1])[:top]


def top_collectives(hlo_text: str, n: int = 12) -> list[dict]:
    """Largest individual collective ops with their source metadata (attribution
    for the §Perf loop: WHICH all-reduce is eating the wire)."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        sig, kind = m.group(1), m.group(2)
        g = _group_size(line)
        b = _op_bytes(sig, kind, bool(m.group(3)), g)
        meta = re.search(r'op_name="([^"]+)"', line)
        out.append({"kind": kind, "bytes": b, "group": g, "sig": sig[:60],
                    "op_name": (meta.group(1)[-110:] if meta else "")})
    out.sort(key=lambda d: -d["bytes"])
    return out[:n]
