"""Three-term roofline from the compiled dry-run artifact.

  compute    = global_HLO_FLOPs / (chips * peak)
  memory     = global_HLO_bytes / (chips * hbm_bw)
  collective = per_device_collective_bytes / link_bw

``compiled.cost_analysis()`` reports the *per-device* SPMD program, so
global = per_device * chips.  Collective bytes are not in cost_analysis —
we parse the post-SPMD HLO text and sum the operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

MODEL_FLOPS = 6*N*D (train) or 2*N*D (inference) with N the (active) param
count; the ratio MODEL_FLOPS / global_HLO_FLOPs exposes remat/redundancy
overhead (>1 means the compiled program does *less* than the analytic count
would suggest — e.g. factored attention; <1 means recompute/waste).
"""
from __future__ import annotations

import dataclasses
import re

from repro.roofline import hw

_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|f8e4m3|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")
# `%name = TYPE ...` definition lines (TYPE may be a tuple)
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([A-Za-z0-9_.\-]+)\s*=\s*([^=]*?)\s+"
                     r"([a-z][a-z0-9\-]*)\(")
# collective ops: the op name directly follows the result type
_COLL_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(([^)]*)\)")
_OPERAND_RE = re.compile(r"%([A-Za-z0-9_.\-]+)")


def shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * hw.DTYPE_BYTES[dtype]


def type_bytes(type_str: str) -> int:
    return sum(shape_bytes(d, s) for d, s in _SHAPE_RE.findall(type_str))


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-device bytes moved through each collective kind (operand sizes).

    Post-SPMD HLO text references operands by name only, so we first build a
    name -> result-type-bytes map from every definition line, then sum the
    operand sizes of each all-reduce / all-gather / reduce-scatter /
    all-to-all / collective-permute (async ``-start`` forms included, their
    ``-done`` halves not double-counted).
    """
    defs: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            name, type_str, _ = m.groups()
            defs[name] = type_bytes(type_str)
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_OP_RE.search(line)
        if not m:
            continue
        kind, operands = m.group(1), m.group(2)
        total = sum(defs.get(nm, 0) for nm in _OPERAND_RE.findall(operands))
        if total == 0:  # fall back to the result type (== operand for AR)
            head = line.split(f" {kind}", 1)[0]
            total = type_bytes(head)
        out[kind] = out.get(kind, 0) + total
    return out


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops_global: float
    hlo_bytes_global: float
    coll_bytes_device: float
    coll_breakdown: dict
    chips: int

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """No-overlap upper bound (sum) — we also report max() as the
        perfectly-overlapped bound."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """compute_s / step_time_s: 1.0 = pure compute-bound (ideal)."""
        t = self.step_time_s
        return self.compute_s / t if t > 0 else 0.0

    def to_dict(self):
        return {**dataclasses.asdict(self),
                "dominant": self.dominant,
                "step_time_s": self.step_time_s,
                "roofline_fraction": self.roofline_fraction}


def analyze(cost: dict, hlo_text: str, chips: int, device_kind: str,
            trip_factor: float = 1.0) -> Roofline:
    """Roofline terms against the published peaks of ``device_kind``
    (``hw.peaks``; an unlisted device raises).  ``trip_factor`` corrects
    XLA's known while-loop undercount: HLO cost analysis counts each loop
    body ONCE regardless of trip count (verified on this backend — see
    EXPERIMENTS.md §Dry-run).  Our models put virtually
    all compute inside ``lax.scan`` (layers x microbatches x token steps), so
    we scale per-device flops/bytes/collectives by the statically-known trip
    product (``scan_trip_factor`` below).  Loop-external work (embeddings,
    loss, optimizer update) gets over-scaled by the same factor — a bounded,
    documented distortion (small vs. L x per-layer cost)."""
    flops_dev = float(cost.get("flops", 0.0)) * trip_factor
    bytes_dev = float(cost.get("bytes accessed", 0.0)) * trip_factor
    coll = collective_bytes(hlo_text)
    coll_dev = float(sum(coll.values())) * trip_factor
    pk = hw.peaks(device_kind)
    return Roofline(
        compute_s=flops_dev / pk.flops_bf16,
        memory_s=bytes_dev / pk.hbm_bw,
        collective_s=coll_dev / pk.ici_bw,
        hlo_flops_global=flops_dev * chips,
        hlo_bytes_global=bytes_dev * chips,
        coll_bytes_device=coll_dev,
        coll_breakdown=coll,
        chips=chips,
    )


def scan_trip_factor(cfg, shape_kind: str, seq: int, global_batch: int,
                     microbatch: int) -> float:
    """Product of the statically-known trip counts along the dominant path.

    train: layers-scan (fwd body + bwd body both scale with L) x grad-accum
    microbatch trips.  prefill/decode: layers-scan; SSM/hybrid/enc-dec
    prefill additionally scans over tokens.  The SSD inter-chunk state scan
    is flop-negligible (elementwise) and left uncorrected.
    """
    layers = cfg.n_layers + (cfg.enc_layers if shape_kind == "train" else 0)
    if shape_kind == "train":
        mb_trips = (global_batch // microbatch) if microbatch else 1
        return float(max(layers, 1) * max(mb_trips, 1))
    if shape_kind == "prefill":
        sequential = (cfg.family in ("ssm", "hybrid", "encdec")
                      and not cfg.parallel_prefill)
        token_scan = seq if sequential else 1
        return float(max(cfg.n_layers, 1) * token_scan)
    return float(max(cfg.n_layers, 1))  # decode


# ---------------------------------------------------------------------------
# analytic MODEL_FLOPS
# ---------------------------------------------------------------------------


def count_params(cfg, active_only: bool = False) -> int:
    """Analytic parameter count from the config (embedding included once)."""
    dm, dff, V = cfg.d_model, cfg.d_ff, cfg.vocab
    attn = 0
    if cfg.n_heads:
        attn = dm * cfg.n_heads * cfg.d_head + 2 * dm * cfg.n_kv_heads * cfg.d_head \
            + cfg.n_heads * cfg.d_head * dm
    mlp = dm * dff * (3 if cfg.mlp_gated else 2) if dff else 0
    ssm = 0
    if cfg.ssm_state:
        d_inner = cfg.ssm_expand * dm
        H = d_inner // cfg.ssm_head_dim
        proj = dm * (2 * d_inner + 2 * cfg.ssm_state + H)
        ssm = proj + d_inner * dm + cfg.ssm_conv * (d_inner + 2 * cfg.ssm_state)
    emb = V * dm * (1 if cfg.tie_embeddings else 2)

    if cfg.family in ("dense", "vlm"):
        core = cfg.n_layers * (attn + mlp)
    elif cfg.family == "moe":
        e = cfg.moe_top_k if active_only else cfg.n_experts
        core = cfg.n_layers * (attn + mlp * e + dm * cfg.n_experts)
    elif cfg.family == "ssm":
        core = cfg.n_layers * ssm
    elif cfg.family == "hybrid":
        n_attn_calls = cfg.n_layers // cfg.attn_every
        shared = attn + mlp  # one shared block
        core = cfg.n_layers * ssm + (shared if not active_only
                                     else shared)  # params counted once
        if active_only:
            core = cfg.n_layers * ssm + n_attn_calls * (attn + mlp)
    elif cfg.family == "encdec":
        core = cfg.n_layers * (2 * attn + mlp) + cfg.enc_layers * (attn + mlp)
    else:
        core = 0
    return core + emb


def model_flops(cfg, tokens: int, kind: str) -> float:
    """6*N*D for train, 2*N*D for inference (active params for MoE)."""
    n = count_params(cfg, active_only=True)
    return (6.0 if kind == "train" else 2.0) * n * tokens
