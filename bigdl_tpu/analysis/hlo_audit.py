"""HLO program auditor: static passes over every fused step's lowered IR.

PR 8's compile path already lowers every fused step to StableHLO (the
cache key is its digest) — this module finally LOOKS at that text.
Three pass families run at compile (or cache warm-load) time, hooked
into ``compile_cache.CachedStep``, and offline over a persisted cache
directory (``python -m bigdl_tpu.analysis.hlo_audit <cacheDir>``):

1. **collective contracts** (``bigdl.audit.collectives``) — every
   all-reduce / all-gather / reduce-scatter / all-to-all /
   collective-permute is extracted with its operand/result byte counts
   and replica groups, aggregated into a per-step communication budget
   (``Audit/collective_bytes`` + per-kind op counters in the telemetry
   registry), and checked against the :class:`~bigdl_tpu.analysis.
   program_contracts.StepContract` the owning trainer declared.  An
   undeclared kind, an op-count over ``max_ops`` or under ``min_ops``
   (the bucketed ZeRO-1 schedule promises a collective PER BUCKET — a
   missing one is a silently-unreduced parameter range), or aggregate
   traffic over ``max_bytes`` is a structured
   :class:`~bigdl_tpu.analysis.program_contracts.
   ProgramContractViolation` naming the HLO op, its shapes, and the
   owning step.
2. **precision drift** (``bigdl.audit.precision``) — any f64 op
   anywhere (x64 drift at the level that actually executes), and any
   f32-operand ``dot_general``/``convolution`` inside a program whose
   declared activation dtype is bf16 (an upcast the module-level
   checker can miss once jit fuses it).
3. **memory/layout budgets** (``bigdl.audit.memory``) — peak-buffer
   estimate from ``compiled.memory_analysis()`` plus a transpose
   census (generalizing PR 1's one-off ResNet HLO assertion): rank-4
   transposes beyond the contract's ``max_rank4_transposes`` are a
   violation; the census and peak bytes are always exported, so the
   offline audit of a persisted cache against the committed
   ``audit_baselines.json`` (``python -m bigdl_tpu.analysis.hlo_audit
   <cache dir> --baselines audit_baselines.json``) catches regressions
   rather than absolutes.

Modes mirror ``bigdl.analysis.*``: ``strict`` raises
:class:`ProgramContractError` at compile time, ``warn`` logs the
structured report, ``off`` disables the pass (tier-1 arms all three
strict via the conftest autouse fixture).
"""

from __future__ import annotations

import json
import logging
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from bigdl_tpu.analysis.program_contracts import (COLLECTIVE_KINDS,
                                                  ProgramContractError,
                                                  ProgramContractViolation,
                                                  StepContract)

logger = logging.getLogger("bigdl_tpu")

_MODES = ("strict", "warn", "off")
_PASSES = ("collectives", "precision", "memory")


def audit_mode(key: str, default: str = "warn") -> str:
    """Resolve an audit pass's mode from ``bigdl.audit.<key>`` —
    identical semantics to ``analysis.pass_mode`` (unknown values
    degrade to ``off``, loudly)."""
    from bigdl_tpu.utils import config
    mode = str(config.get_property(f"bigdl.audit.{key}", default)).lower()
    if mode not in _MODES:
        logger.warning("bigdl.audit.%s=%r is not one of %s — pass disabled",
                       key, mode, _MODES)
        return "off"
    return mode


def armed() -> bool:
    """True when at least one audit pass is not ``off`` — the gate the
    compile hook checks before paying for ``lowered.as_text()``."""
    return any(audit_mode(k) != "off" for k in _PASSES)


# ---- StableHLO text census --------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "f8E4M3FN": 1, "f8E5M2": 1, "f8E4M3": 1, "f8E3M4": 1,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i4": 1, "ui4": 1, "i1": 1,
    "complex<f32>": 8, "complex<f64>": 16,
}

_COLLECTIVE_RE = re.compile(
    r'"stablehlo\.(all_reduce|all_gather|reduce_scatter|all_to_all|'
    r'collective_permute|collective_broadcast)"')
_GROUPS_RE = re.compile(
    r'(?:replica_groups|source_target_pairs)\s*=\s*dense<(\[.*?\]|)>')
_FUNC_TYPE_RE = re.compile(r':\s*\(([^()]*)\)\s*->\s*(.+)$')
_TENSOR_RE = re.compile(r'tensor<((?:[^<>]|<[^<>]*>)*)>')
_DIMS_DTYPE_RE = re.compile(r'^((?:\d+x)*)(.+)$')
_OPNAME_RE = re.compile(r'stablehlo\.(\w+)')
_TRANSPOSE_DIMS_RE = re.compile(
    r'stablehlo\.transpose.*?(?:dims|permutation)\s*=\s*(?:dense<)?'
    r'\[([0-9, ]*)\]')
_F64_RE = re.compile(r'\bc?f64\b|complex<f64>')


def _tensor_bytes(spec: str) -> int:
    """Byte size of one ``tensor<...>`` body (``2x4xf32`` → 32; a
    dynamic/unknown dtype estimates at 4 bytes per element)."""
    m = _DIMS_DTYPE_RE.match(spec.strip())
    if m is None:
        return 0
    n = 1
    for d in m.group(1).split("x"):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(m.group(2).strip(), 4)


def _side_bytes(side: str) -> int:
    return sum(_tensor_bytes(t) for t in _TENSOR_RE.findall(side))


@dataclass(frozen=True)
class CollectiveOp:
    """One extracted collective: op name, kind (contract vocabulary),
    operand/result byte totals, the raw type signature, and the replica
    groups / source-target pairs attribute."""

    op: str
    kind: str
    operand_bytes: int
    result_bytes: int
    types: str
    groups: str

    @property
    def traffic_bytes(self) -> int:
        """The per-op budget charge: max(operand, result) — an
        all-gather's cost is its full result, a reduce-scatter's its
        full operand."""
        return max(self.operand_bytes, self.result_bytes)


@dataclass
class ProgramCensus:
    """Everything the parser extracted from one step's StableHLO."""

    label: str
    collectives: List[CollectiveOp] = field(default_factory=list)
    f64_ops: List[str] = field(default_factory=list)
    f32_compute_ops: List[str] = field(default_factory=list)
    transposes: int = 0
    rank4_transposes: int = 0
    peak_bytes: Optional[int] = None

    @property
    def collective_bytes(self) -> int:
        return sum(c.traffic_bytes for c in self.collectives)

    def by_kind(self) -> Dict[str, Dict[str, int]]:
        out: Dict[str, Dict[str, int]] = {}
        for c in self.collectives:
            slot = out.setdefault(c.kind, {"ops": 0, "bytes": 0})
            slot["ops"] += 1
            slot["bytes"] += c.traffic_bytes
        return out

    def summary(self) -> Dict[str, Any]:
        """JSON-safe digest — what the compile cache persists in its
        entry manifest (the offline auditor's input) and what the bench
        audit leg records."""
        return {
            "label": self.label,
            "by_kind": self.by_kind(),
            "collective_bytes": self.collective_bytes,
            "transposes": self.transposes,
            "rank4_transposes": self.rank4_transposes,
            "f64_ops": len(self.f64_ops),
            "f32_compute_ops": len(self.f32_compute_ops),
            "peak_bytes": self.peak_bytes,
        }


def parse_stablehlo(label: str, text: str) -> ProgramCensus:
    """One linear scan over the StableHLO text.  Region-bearing
    collectives (``all_reduce``/``reduce_scatter`` carry their reduction
    computation as a region) put their type signature on the closing
    ``})`` line — the scanner tracks region depth to find it."""
    census = ProgramCensus(label=label)
    lines = text.splitlines()
    i = 0
    while i < len(lines):
        line = lines[i]
        if "stablehlo." not in line:
            i += 1
            continue
        m = _COLLECTIVE_RE.search(line)
        if m:
            op = f"stablehlo.{m.group(1)}"
            kind = m.group(1).replace("_", "-")
            gm = _GROUPS_RE.search(line)
            groups = gm.group(1) if gm else ""
            sig = line
            if _FUNC_TYPE_RE.search(line) is None:
                # region op: chase the closing "}) : (...) -> ..." line
                depth = line.count("({") - line.count("})")
                while depth > 0 and i + 1 < len(lines):
                    i += 1
                    depth += lines[i].count("({") - lines[i].count("})")
                sig = lines[i]
            ft = _FUNC_TYPE_RE.search(sig)
            operand_b = result_b = 0
            types = ""
            if ft:
                operand_b = _side_bytes(ft.group(1))
                result_b = _side_bytes(ft.group(2))
                types = f"({ft.group(1).strip()}) -> {ft.group(2).strip()}"
            census.collectives.append(CollectiveOp(
                op=op, kind=kind, operand_bytes=operand_b,
                result_bytes=result_b, types=types, groups=groups))
            i += 1
            continue
        if _F64_RE.search(line):
            om = _OPNAME_RE.search(line)
            census.f64_ops.append(
                f"stablehlo.{om.group(1) if om else '?'}: {line.strip()}")
        if "stablehlo.dot_general" in line or "stablehlo.convolution" in line:
            ft = _FUNC_TYPE_RE.search(line)
            if ft and any(
                    _DIMS_DTYPE_RE.match(t.strip()) and
                    _DIMS_DTYPE_RE.match(t.strip()).group(2).strip() == "f32"
                    for t in _TENSOR_RE.findall(ft.group(1))):
                om = _OPNAME_RE.search(line)
                census.f32_compute_ops.append(
                    f"stablehlo.{om.group(1)}: "
                    f"({ft.group(1).strip()}) -> {ft.group(2).strip()}")
        if "stablehlo.transpose" in line:
            tm = _TRANSPOSE_DIMS_RE.search(line)
            if tm:
                census.transposes += 1
                if len([d for d in tm.group(1).split(",") if
                        d.strip()]) == 4:
                    census.rank4_transposes += 1
        i += 1
    return census


def peak_buffer_bytes(compiled) -> Optional[int]:
    """Total device footprint estimate from the executable's memory
    analysis: arguments + outputs + temporaries.  Backends (and
    deserialized cache loads) that cannot answer return None — the
    memory pass then only runs its transpose census."""
    try:
        ma = compiled.memory_analysis()
        return int(getattr(ma, "argument_size_in_bytes", 0) +
                   getattr(ma, "output_size_in_bytes", 0) +
                   getattr(ma, "temp_size_in_bytes", 0))
    except Exception:
        return None


# ---- the three pass families ------------------------------------------------


def _check_collectives(census: ProgramCensus,
                       contract: Optional[StepContract]
                       ) -> List[ProgramContractViolation]:
    if contract is None:
        return []
    out: List[ProgramContractViolation] = []
    by_kind = census.by_kind()
    for kind, agg in sorted(by_kind.items()):
        ops = [c for c in census.collectives if c.kind == kind]
        bound = contract.bound_for(kind)
        shapes = "; ".join(c.types or c.op for c in ops[:4])
        if bound is None:
            declared = ", ".join(b.kind for b in contract.collectives) \
                or "none"
            out.append(ProgramContractViolation(
                step=census.label, pass_name="collective", op=ops[0].op,
                detail=f"{agg['ops']} undeclared {kind} op(s) "
                       f"({agg['bytes']} bytes: {shapes}) — the contract "
                       f"declares only: {declared}"))
            continue
        if bound.max_ops is not None and agg["ops"] > bound.max_ops:
            out.append(ProgramContractViolation(
                step=census.label, pass_name="collective", op=ops[0].op,
                detail=f"{agg['ops']} {kind} op(s) exceed the declared "
                       f"max of {bound.max_ops} ({shapes}) — declared "
                       f"for: {bound.reason or 'unspecified'}"))
        if bound.min_ops is not None and agg["ops"] < bound.min_ops:
            out.append(ProgramContractViolation(
                step=census.label, pass_name="collective", op=ops[0].op,
                detail=f"only {agg['ops']} {kind} op(s) where the contract "
                       f"requires at least {bound.min_ops} ({shapes}) — a "
                       f"missing collective means a data range silently "
                       f"skipped its exchange; declared for: "
                       f"{bound.reason or 'unspecified'}"))
        if bound.max_bytes is not None and agg["bytes"] > bound.max_bytes:
            out.append(ProgramContractViolation(
                step=census.label, pass_name="collective", op=ops[0].op,
                detail=f"{kind} traffic {agg['bytes']} bytes exceeds the "
                       f"declared budget of {bound.max_bytes} bytes "
                       f"({shapes})"))
    # a declared kind with an op-count floor that the census does not
    # contain AT ALL never enters the loop above — flag it here (the
    # fully-dropped-collective case)
    for bound in contract.collectives:
        if (getattr(bound, "min_ops", None) and
                bound.min_ops > 0 and bound.kind not in by_kind):
            out.append(ProgramContractViolation(
                step=census.label, pass_name="collective",
                op=f"stablehlo.{bound.kind.replace('-', '_')}",
                detail=f"no {bound.kind} op in the program where the "
                       f"contract requires at least {bound.min_ops} — "
                       f"declared for: {bound.reason or 'unspecified'}"))
    return out


def _check_precision(census: ProgramCensus,
                     contract: Optional[StepContract]
                     ) -> List[ProgramContractViolation]:
    out: List[ProgramContractViolation] = []
    if census.f64_ops:
        out.append(ProgramContractViolation(
            step=census.label, pass_name="precision",
            op=census.f64_ops[0].split(":")[0],
            detail=f"{len(census.f64_ops)} f64 op(s) in the program — "
                   f"x64 drift at execution level (first: "
                   f"{census.f64_ops[0][:160]})"))
    if (contract is not None and contract.activation_dtype == "bf16"
            and census.f32_compute_ops):
        out.append(ProgramContractViolation(
            step=census.label, pass_name="precision",
            op=census.f32_compute_ops[0].split(":")[0],
            detail=f"{len(census.f32_compute_ops)} f32-operand compute "
                   f"op(s) in a program whose declared activation dtype "
                   f"is bf16 (first: {census.f32_compute_ops[0][:160]})"))
    return out


def _check_memory(census: ProgramCensus,
                  contract: Optional[StepContract]
                  ) -> List[ProgramContractViolation]:
    out: List[ProgramContractViolation] = []
    if (contract is not None and
            contract.max_rank4_transposes is not None and
            census.rank4_transposes > contract.max_rank4_transposes):
        out.append(ProgramContractViolation(
            step=census.label, pass_name="memory", op="stablehlo.transpose",
            detail=f"{census.rank4_transposes} rank-4 transposes exceed "
                   f"the declared layout budget of "
                   f"{contract.max_rank4_transposes} — an interior "
                   f"NCHW<->NHWC flip crept back in"))
    return out


# ---- report + entry points --------------------------------------------------


@dataclass
class AuditReport:
    """One audited program: its census, the violations each armed pass
    found, and which of those were found under strict mode."""

    census: ProgramCensus
    violations: List[ProgramContractViolation] = field(default_factory=list)
    strict_violations: List[ProgramContractViolation] = \
        field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def __str__(self):
        c = self.census
        head = (f"program audit [{c.label}]: "
                f"{len(c.collectives)} collective(s) "
                f"({c.collective_bytes} bytes), "
                f"{c.rank4_transposes}/{c.transposes} rank-4 transposes, "
                f"peak {c.peak_bytes if c.peak_bytes is not None else '?'} "
                f"bytes")
        if self.ok:
            return head + " — no violations"
        return "\n".join([head + f" — {len(self.violations)} violation(s)"]
                         + [f"  {v}" for v in self.violations])

    def raise_or_warn(self) -> "AuditReport":
        """Strict-mode findings raise :class:`ProgramContractError`
        (carrying every violation); warn-mode findings log."""
        if self.strict_violations:
            raise ProgramContractError(str(self), self.violations)
        if self.violations:
            logger.warning("%s", self)
        return self


def _export_metrics(census: ProgramCensus) -> None:
    from bigdl_tpu import telemetry
    telemetry.gauge("Audit/collective_bytes",
                    labels={"step": census.label},
                    help="per-step aggregate collective traffic "
                         "(max(operand, result) per op)"
                    ).set(census.collective_bytes)
    for kind, agg in census.by_kind().items():
        telemetry.counter("Audit/collective_ops",
                          labels={"step": census.label, "kind": kind},
                          help="collectives extracted per audited "
                               "program").inc(agg["ops"])
    telemetry.gauge("Audit/rank4_transposes",
                    labels={"step": census.label},
                    help="rank-4 transposes in the audited program"
                    ).set(census.rank4_transposes)
    if census.peak_bytes is not None:
        telemetry.gauge("Audit/peak_bytes", labels={"step": census.label},
                        help="argument+output+temp buffer estimate"
                        ).set(census.peak_bytes)


def audit_step(label: str, hlo_text: str, compiled=None,
               contract: Optional[StepContract] = None,
               topology: Optional[Dict[str, Any]] = None) -> AuditReport:
    """Run every armed pass over one lowered program and return the
    report WITHOUT raising (callers decide via
    :meth:`AuditReport.raise_or_warn` — the compile hook raises after
    the census is safely recorded, the offline CLI never raises).

    ``contract`` defaults to the live/registered contract for
    ``label``; pass ``compiled`` (a jax Compiled/Loaded executable) to
    include the peak-buffer estimate."""
    from bigdl_tpu.analysis import program_contracts
    if contract is None:
        contract = program_contracts.lookup(label)
    census = parse_stablehlo(label, hlo_text)
    if compiled is not None:
        census.peak_bytes = peak_buffer_bytes(compiled)
    report = AuditReport(census=census)
    for pass_key, checker in (("collectives", _check_collectives),
                              ("precision", _check_precision),
                              ("memory", _check_memory)):
        mode = audit_mode(pass_key)
        if mode == "off":
            continue
        found = checker(census, contract)
        report.violations.extend(found)
        if mode == "strict":
            report.strict_violations.extend(found)
    _export_metrics(census)
    if report.violations:
        from bigdl_tpu import telemetry
        for v in report.violations:
            telemetry.counter("Audit/violations",
                              labels={"step": v.step, "pass": v.pass_name},
                              help="program contract violations"
                              ).inc()
    return report


# ---- offline mode over a persisted compile cache ----------------------------


def load_baselines(path: str) -> Dict[str, Dict[str, Any]]:
    with open(path) as f:
        data = json.load(f)
    return data.get("steps", data)


def check_against_baseline(label: str, summary: Dict[str, Any],
                           baseline: Dict[str, Any],
                           bytes_tolerance: float = 1.25,
                           transpose_slack: int = 0) -> List[str]:
    """Regression check of one census summary against its committed
    baseline: collective bytes within ``bytes_tolerance``x, rank-4
    transposes within ``+transpose_slack``, no new collective kind.
    Returns problem strings (empty = within tolerance)."""
    problems: List[str] = []
    base_bytes = baseline.get("collective_bytes", 0)
    if summary.get("collective_bytes", 0) > base_bytes * bytes_tolerance \
            + 1024:
        problems.append(
            f"{label}: collective traffic {summary['collective_bytes']} B "
            f"regressed past {bytes_tolerance}x baseline ({base_bytes} B)")
    base_t = baseline.get("rank4_transposes", 0)
    if summary.get("rank4_transposes", 0) > base_t + transpose_slack:
        problems.append(
            f"{label}: rank-4 transpose census "
            f"{summary['rank4_transposes']} regressed past baseline "
            f"{base_t} (+{transpose_slack} slack)")
    new_kinds = set(summary.get("by_kind", {})) - \
        set(baseline.get("by_kind", {}))
    if new_kinds:
        problems.append(
            f"{label}: new collective kind(s) vs baseline: "
            f"{sorted(new_kinds)}")
    return problems


def audit_cache_dir(path: str, baselines: Optional[Dict[str, Any]] = None
                    ) -> Tuple[List[str], List[str]]:
    """Audit every committed entry of a persisted compile cache from
    its manifest's recorded census (entries stored while the audit was
    armed).  Returns (report_lines, problems) — problems non-empty
    means the offline audit fails."""
    from bigdl_tpu.analysis import program_contracts
    lines: List[str] = []
    problems: List[str] = []
    try:
        names = sorted(os.listdir(path))
    except OSError as e:
        return [], [f"cache dir {path!r} unreadable: {e}"]
    seen = 0
    for name in names:
        if not name.endswith(".commit"):
            continue
        key = name[:-len(".commit")]
        try:
            with open(os.path.join(path, f"{key}.json")) as f:
                manifest = json.load(f)
        except Exception as e:
            problems.append(f"entry {key}: manifest unreadable ({e})")
            continue
        seen += 1
        label = manifest.get("label", "?")
        summary = manifest.get("audit")
        if summary is None:
            lines.append(f"entry {key} [{label}]: no census recorded "
                         "(stored with the audit off) — skipped")
            continue
        contract = program_contracts.lookup(label)
        lines.append(
            f"entry {key} [{label}]: "
            f"{sum(a['ops'] for a in summary.get('by_kind', {}).values())} "
            f"collective(s), {summary.get('collective_bytes', 0)} bytes, "
            f"{summary.get('rank4_transposes', 0)} rank-4 transposes")
        if contract is not None:
            for kind in sorted(summary.get("by_kind", {})):
                if contract.bound_for(kind) is None:
                    problems.append(str(ProgramContractViolation(
                        step=label, pass_name="collective",
                        op=f"stablehlo.{kind.replace('-', '_')}",
                        detail=f"persisted entry {key} contains an "
                               f"undeclared {kind} "
                               f"({summary['by_kind'][kind]['ops']} op(s), "
                               f"{summary['by_kind'][kind]['bytes']} "
                               f"bytes)")))
        if summary.get("f64_ops", 0):
            problems.append(str(ProgramContractViolation(
                step=label, pass_name="precision", op="f64",
                detail=f"persisted entry {key} contains "
                       f"{summary['f64_ops']} f64 op(s)")))
        if baselines is not None and label in baselines:
            problems.extend(check_against_baseline(
                label, summary, baselines[label]))
    if seen == 0:
        lines.append(f"no committed entries under {path!r}")
    return lines, problems


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m bigdl_tpu.analysis.hlo_audit",
        description="offline HLO audit over a persisted compile cache")
    ap.add_argument("cache_dir", help="bigdl.compile.cacheDir to audit")
    ap.add_argument("--baselines", default=None,
                    help="audit_baselines.json to regression-check "
                         "against (optional)")
    args = ap.parse_args(argv)
    baselines = load_baselines(args.baselines) if args.baselines else None
    lines, problems = audit_cache_dir(args.cache_dir, baselines)
    for ln in lines:
        print(ln)
    for p in problems:
        print(f"VIOLATION: {p}")
    print(f"hlo_audit: {len(problems)} problem(s)")
    return 1 if problems else 0


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess
    import sys
    sys.exit(main(sys.argv[1:]))
