"""Roofline-term extraction from compiled XLA artifacts.

``hlo_instructions`` reads each instruction's opcode and ``op_name``
metadata (the ``jax.named_scope`` path that issued it) from a compiled
module's text.  ``collective_stats`` parses the post-optimization HLO text
and models the
per-device ICI wire bytes of every collective with ring-algorithm formulas:

    all-gather        (n-1)/n * result_bytes
    reduce-scatter    (n-1)/n * operand_bytes
    all-reduce        2 (n-1)/n * operand_bytes      (RS + AG)
    all-to-all        (n-1)/n * operand_bytes
    collective-permute  operand_bytes

where n is the replica-group size parsed from the op.  ``roofline`` converts
cost_analysis + collective bytes into the three §Roofline terms for TPU v5e
(197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI — spec constants).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

# --- TPU v5e hardware constants (per chip) ---------------------------------
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link (one direction)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16, "token": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\(?[^=]*?\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?(?:\.\d+)?\(",
)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes_list(sig: str) -> list[int]:
    out = []
    for dtype, dims in _SHAPE_RE.findall(sig):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append(n * _DTYPE_BYTES[dtype])
    return out


def _shape_bytes(sig: str, *, is_start: bool = False) -> int:
    """Byte size of an op result signature.  Plain ops may return tuples of
    reduced tensors (sum them); async ``-start`` ops return (operand, result)
    pairs (take the max = the gathered/reduced result)."""
    sizes = _shape_bytes_list(sig)
    if not sizes:
        return 0
    return max(sizes) if is_start else sum(sizes)


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    return default


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
_INSTR_RE = re.compile(r"^\s+(ROOT\s+)?%?([^\s=]+) = (.*)$")
_OPCODE_RE = re.compile(r"[\]})] ([a-z][a-z0-9_\-]*)\(")
_OP_NAME_RE = re.compile(r'\bop_name="([^"]*)"')
# computations that run as device ops of their own: loop bodies and
# conditions, conditional branches, called computations
_RUNS_RE = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([^\s,}]+)")
_BRANCHES_RE = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_CALLS_RE = re.compile(r"\bcalls=%?([^\s,}]+)")


def hlo_instructions(hlo_text: str) -> dict[str, tuple[str, Optional[str]]]:
    """``{name: (opcode, op_name)}`` for every instruction of a compiled HLO
    module's text (``Compiled.as_text()``) that runs as a device op: those
    of the entry computation and of the loop bodies, conditions, branches
    and calls it reaches, not those inside fusions or reducers.  ``op_name``
    is the instruction's metadata (its ``jax.named_scope`` path).  An
    instruction the compiler made without one takes its fused root's, or
    else that of the loop, branch or call it runs in; None at the top level.
    Names are unique within a module, not across modules."""
    comps: dict[str, list] = {}
    roots: dict[str, Optional[str]] = {}
    entry, cur = None, None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m:
            cur = m.group(1)
            comps[cur] = []
            entry = cur if line.startswith("ENTRY") else entry
            continue
        m = _INSTR_RE.match(line)
        if not m or cur is None:
            continue
        rest = m.group(3)
        op = _OPCODE_RE.search(rest)
        meta = _OP_NAME_RE.search(rest)
        ins = (m.group(2), op.group(1) if op else "",
               meta.group(1) if meta else None, rest)
        comps[cur].append(ins)
        if m.group(1):
            roots[cur] = ins[2]
    out: dict[str, tuple[str, Optional[str]]] = {}
    todo, seen = [(entry, None)], set()
    while todo:
        comp, outer = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, opcode, op_name, rest in comps[comp]:
            called = _CALLS_RE.search(rest)
            runs = _RUNS_RE.findall(rest)
            for branches in _BRANCHES_RE.findall(rest):
                runs += [b.strip().lstrip("%") for b in branches.split(",")]
            if opcode == "fusion":
                if op_name is None and called:
                    op_name = roots.get(called.group(1))
            elif called:
                runs.append(called.group(1))
            op_name = outer if op_name is None else op_name
            todo += [(c, op_name) for c in runs]
            out[name] = (opcode, op_name)
    return out


def scope_path(op_name: Optional[str], stage: str, within: str) -> tuple:
    """The innermost component of an ``op_name`` path that starts with
    ``stage``, then the components under it that start with ``within``:
    ``("frontier.reorder", "iru.banked", "iru.rows")``.  Empty when no
    component starts with ``stage``."""
    parts = (op_name or "").split("/")
    at = [i for i, c in enumerate(parts) if c.startswith(stage)]
    if not at:
        return ()
    return (parts[at[-1]],) + tuple(
        c for c in parts[at[-1] + 1:] if c.startswith(within))


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    result_bytes: dict            # sum of result shapes per op kind
    wire_bytes_per_device: float  # ring-modeled ICI payload

    def total_result_bytes(self) -> float:
        return float(sum(self.result_bytes.values()))


def collective_stats(hlo_text: str, n_devices: int) -> CollectiveStats:
    counts: dict = {}
    rbytes: dict = {}
    wire = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        if m.group(3) == "-done":
            continue  # async pair: the -start op already carried the payload
        sig, kind = m.group(1), m.group(2)
        b = _shape_bytes(sig, is_start=m.group(3) == "-start")
        n = _group_size(line, n_devices)
        frac = (n - 1) / max(n, 1)
        if kind == "all-gather":
            payload = frac * b                      # result is gathered size
        elif kind == "all-reduce":
            payload = 2 * frac * b                  # operand==result
        elif kind == "reduce-scatter":
            payload = frac * b * n                  # operand = result * n
        elif kind == "all-to-all":
            payload = frac * b
        else:  # collective-permute
            payload = b
        counts[kind] = counts.get(kind, 0) + 1
        rbytes[kind] = rbytes.get(kind, 0) + b
        wire += payload
    return CollectiveStats(counts, rbytes, wire)


@dataclasses.dataclass
class Roofline:
    """Three roofline terms from the compiled PER-DEVICE SPMD module.

    ``compiled.cost_analysis()`` is computed on the partitioned program, so
    ``flops`` and ``hbm_bytes`` are already per-device; the collective wire
    bytes are ring-modeled per device too.  No further division by chips."""

    flops: float
    hbm_bytes: float
    wire_bytes: float
    n_devices: int

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.wire_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    def as_dict(self) -> dict:
        return {
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "hlo_flops": self.flops,
            "hlo_bytes": self.hbm_bytes,
            "wire_bytes_per_device": self.wire_bytes,
            "n_devices": self.n_devices,
        }


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N_active·D (train) or 2·N_active·D (fwd) per token,
    plus the attention score/value flops against the live KV length (which
    6·N·D famously omits — dominant for decode against a 32k cache)."""
    n_active = active_params(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    total = mult * n_active * tokens
    # attention qk^T + av flops per token: 4 * H * hd * kv_len per attn layer
    n_attn = sum(1 for k in cfg.layer_kinds() if k == "attn")
    if n_attn and cfg.n_heads:
        if shape.kind == "decode":
            kv = shape.seq_len
        else:
            kv = shape.seq_len / 2.0          # causal average
        if cfg.attn_window is not None:
            kv = min(kv, cfg.attn_window)
        per_tok = 4.0 * cfg.n_heads * cfg.head_dim * kv * n_attn
        total += (mult / 2.0) * per_tok * tokens
    return total


def active_params(cfg) -> float:
    """Per-token active parameter count (MoE counts top_k + shared only)."""
    total = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    for i, kind in enumerate(cfg.layer_kinds()):
        if kind == "attn":
            total += cfg._attn_params()
        else:
            total += cfg._mamba_params()
        if cfg.is_moe_layer(i):
            m = cfg.moe
            mats = 3 if cfg.ffn_type == "swiglu" else 2
            per = mats * cfg.d_model * m.d_ff
            total += (m.top_k + m.n_shared_experts) * per + cfg.d_model * m.n_experts
        elif cfg.d_ff:
            mats = 3 if cfg.ffn_type == "swiglu" else 2
            total += mats * cfg.d_model * cfg.d_ff
        total += 2 * cfg.d_model
    if cfg.encoder_layers:
        mats = 3 if cfg.ffn_type == "swiglu" else 2
        total += cfg.encoder_layers * (cfg._attn_params() + mats * cfg.d_model * cfg.d_ff)
        total += cfg.n_layers * cfg._attn_params()
    return float(total)
