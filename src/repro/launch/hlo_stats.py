"""Extract roofline terms from compiled XLA artifacts.

``cost_analysis`` gives HLO FLOPs and bytes accessed; collective traffic is
NOT in cost_analysis, so we parse the optimized HLO text and sum the
result-shape bytes of every collective op (all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute).
"""

from __future__ import annotations

import re
from typing import Dict

import numpy as np

# v5e-class hardware constants (per chip)
PEAK_FLOPS = 197e12  # bf16 FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# one shape token: bf16[2048,512]{1,0:T(8,128)} etc.
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)
# the shape group admits the "/*index=5*/" comments XLA prints inside
# tuple shapes of six or more elements (a combined all-reduce on the TPU)
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[\w\[\]{},:()#*/= ]+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)(-start|-done)?\(",
)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_GROUPS_RE = re.compile(r"replica_groups=\{(\{[\d,{} ]*\})\}|replica_groups=\[[^\]]*\]<=\[[^\]]*\]")
_PAIR_RE = re.compile(r"source_target_pairs=\{([\d,{} ]*)\}")


_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?"
)


def _crosses(line: str, boundary: int) -> bool:
    """True if the op's communication groups span the pod boundary."""
    m = re.search(r"replica_groups=\{\{([\d,{} ]*)\}\}", line)
    if m:
        for grp in m.group(1).split("},{"):
            ids = [int(x) for x in grp.replace("{", "").replace("}", "").split(",") if x.strip()]
            if ids and min(ids) < boundary <= max(ids):
                return True
        return False
    m = _PAIR_RE.search(line)
    if m:
        ids = [int(x) for x in m.group(1).replace("{", " ").replace("}", " ").replace(",", " ").split()]
        pairs = list(zip(ids[::2], ids[1::2]))
        return any((a < boundary) != (b < boundary) for a, b in pairs)
    m = _IOTA_RE.search(line)
    if m:
        # iota list: ids = arange(prod(dims)).reshape(dims).transpose(perm)
        # flattened, then chunked into groups of size S.
        g, s_sz = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.transpose(perm)
        flat = ids.reshape(-1)
        for i in range(g):
            grp = flat[i * s_sz : (i + 1) * s_sz]
            if grp.min() < boundary <= grp.max():
                return True
        return False
    return False


def collective_permute_pairs(hlo_text: str):
    """``source_target_pairs`` of every collective-permute in the module,
    as a list of per-op ``[(src, tgt), ...]`` lists.

    Tests use this to assert *where* permutes run, not just how many bytes
    they move — e.g. the pipeline engine's contract that every ens-ring
    hop stays inside one stage (``src % S == tgt % S`` on an (ens, pipe)
    mesh) and stage-boundary hops move exactly one stage forward."""
    out = []
    for line in hlo_text.splitlines():
        op = _OP_RE.match(line)
        if not op or op.group(3) != "collective-permute" or op.group(4) == "-done":
            continue  # pairs live on the sync op or the async -start line
        m = _PAIR_RE.search(line)
        if not m:
            continue
        ids = [
            int(x)
            for x in m.group(1).replace("{", " ").replace("}", " ")
            .replace(",", " ").split()
        ]
        out.append(list(zip(ids[::2], ids[1::2])))
    return out


def collective_bytes(hlo_text: str, pod_boundary: int = 0) -> Dict[str, int]:
    """Per-collective-kind result bytes summed over the module.

    ``pod_boundary`` > 0 also attributes bytes of ops whose replica groups
    span partition ids [0, boundary) and [boundary, ...) — i.e. traffic
    that must cross the pod-to-pod links — under the key "crosspod".
    """
    out = {k: 0 for k in _COLLECTIVES}
    out["crosspod"] = 0
    pending: Dict[str, bool] = {}  # async -start op name -> crosses boundary
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        name, shape_str, kind, suffix = m.groups()
        if suffix == "-start":
            # Async pair: the -start op's result is an (operand, result,
            # ...) tuple, so summing its shape tokens would double count.
            # Bytes come from the matching -done op (whose result is
            # exactly the collective's output); the group metadata lives
            # only here, so remember whether it crosses the boundary.
            pending[name] = bool(pod_boundary) and _crosses(line, pod_boundary)
            continue
        b = _shape_bytes(shape_str)
        out[kind] += b
        if suffix == "-done":
            om = re.match(r"\s*%?([\w.\-]+)", line[m.end():])
            if om and pending.pop(om.group(1), False):
                out["crosspod"] += b
        elif pod_boundary and _crosses(line, pod_boundary):
            out["crosspod"] += b
    return out


def collective_counts(hlo_text: str) -> Dict[str, int]:
    """Number of collective ops per kind, async start/done pairs counted
    once.  Kinds with no ops are present with count 0, so callers can
    assert absence without ``.get``."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m or m.group(4) == "-start":
            continue
        out[m.group(3)] += 1
    return out


def collective_result_dtypes(hlo_text: str) -> Dict[str, set]:
    """Result element dtypes per collective kind actually present, e.g.
    ``{"all-reduce": {"f32"}}``.  Async pairs contribute the -done op's
    result dtype (the collective's real output)."""
    out: Dict[str, set] = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m or m.group(4) == "-start":
            continue
        dts = out.setdefault(m.group(3), set())
        for dt, _ in _SHAPE_RE.findall(m.group(2)):
            if dt in _DTYPE_BYTES:
                dts.add(dt)
    return out


_ALIAS_ENTRY_RE = re.compile(r"\{[\d, ]*\}\s*:\s*\(\s*(\d+)\s*,")


def input_output_aliased_params(hlo_text: str) -> set:
    """Parameter numbers the compiler aliased to outputs, parsed from the
    HloModule header's ``input_output_alias={ {out}: (param, {}, kind) }``
    block.  Empty when donation was dropped or never requested — jit
    flattens pytree args, so each HLO parameter is one donated leaf."""
    m = re.search(r"input_output_alias=\{", hlo_text)
    if not m:
        return set()
    depth, i = 1, m.end()
    while depth and i < len(hlo_text):
        c = hlo_text[i]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
        i += 1
    block = hlo_text[m.end() : i - 1]
    return {int(p) for p in _ALIAS_ENTRY_RE.findall(block)}


def roofline_terms(
    flops: float, bytes_hbm: float, coll: Dict[str, int], chips: int
) -> Dict[str, float]:
    """All inputs are PER-DEVICE: ``compiled.cost_analysis()`` and
    ``compiled.as_text()`` describe the per-partition program, so the
    per-chip roofline terms divide by single-chip peaks only.  (``chips``
    is kept for the global-FLOPs cross-check ``flops * chips ≈ MODEL_FLOPS``.)
    """
    cbytes = float(sum(coll.values()))
    return {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_hbm / HBM_BW,
        "collective_s": cbytes / ICI_BW,
        "collective_bytes": cbytes,
        "global_flops": flops * chips,
    }


def dominant_term(terms: Dict[str, float]) -> str:
    t = {k: terms[k] for k in ("compute_s", "memory_s", "collective_s")}
    return max(t, key=t.get)


def model_flops(n_params: int, n_active: int, tokens: int) -> float:
    """6·N·D rule (dense) / 6·N_active·D (MoE) per the assignment."""
    return 6.0 * n_active * tokens


def summarize(cost: dict, hlo_text: str, chips: int, pod_boundary: int = 0) -> Dict[str, float]:
    flops = float(cost.get("flops", 0.0))
    bts = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text, pod_boundary)
    cross = coll.pop("crosspod", 0)
    terms = roofline_terms(flops, bts, coll, chips)
    terms.update(
        {
            "hlo_flops": flops,
            "hlo_bytes": bts,
            "dominant": dominant_term(terms),
            "bytes_crosspod": float(cross),
            **{f"bytes_{k}": float(v) for k, v in coll.items()},
        }
    )
    return terms
