"""HLO program auditor tests (ISSUE 11 tentpole).

The contract under test (analysis/hlo_audit.py + program_contracts.py):

- the StableHLO census extracts every collective with byte counts and
  replica groups (region ops like all_reduce carry their signature on
  the closing ``})`` line), counts rank-4 transposes, and spots f64 /
  f32-compute drift;
- every fused-step family the trainers build passes its declared
  contract STRICT (the conftest arms all three passes strict for the
  whole tier-1 suite — these tests also assert it directly);
- injected violations are CAUGHT with structured reports naming the
  HLO op and the owning step: a redundant all-gather smuggled into the
  shard_map step (``bigdl.chaos.extraAllGather``) and an f32 upcast in
  a declared-bf16 program (``bigdl.chaos.f32Upcast``);
- the offline mode audits a persisted compile cache from the census
  each manifest recorded, and regression-checks against committed
  baselines.
"""

import json
import os

import numpy as np
import jax
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu import telemetry
from bigdl_tpu.analysis import hlo_audit, program_contracts
from bigdl_tpu.analysis.hlo_audit import (AuditReport, audit_step,
                                          check_against_baseline,
                                          parse_stablehlo)
from bigdl_tpu.analysis.program_contracts import (CollectiveBound,
                                                  ProgramContractError,
                                                  StepContract)
from bigdl_tpu.dataset.dataset import ShardedDataSet
from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.dataset.transformer import SampleToMiniBatch
from bigdl_tpu.optim.optimizer import Optimizer
from bigdl_tpu.utils import config

N_DEV = 8


# ---------------------------------------------------------------------------
# StableHLO census (parser unit tests — synthetic IR, no compiles)
# ---------------------------------------------------------------------------

_SYNTH = """\
module @jit_step attributes {mhlo.num_replicas = 1 : i32} {
  func.func public @main(%arg0: tensor<8x4xf32>) -> tensor<4x32xf32> {
    %0 = "stablehlo.all_gather"(%arg0) <{all_gather_dim = 0 : i64, replica_groups = dense<[[0, 1, 2, 3]]> : tensor<1x4xi64>}> : (tensor<8x4xf32>) -> tensor<32x4xf32>
    %1 = "stablehlo.all_reduce"(%0) <{replica_groups = dense<[[0, 1, 2, 3]]> : tensor<1x4xi64>}> ({
    ^bb0(%a: tensor<f32>, %b: tensor<f32>):
      %s = stablehlo.add %a, %b : tensor<f32>
      stablehlo.return %s : tensor<f32>
    }) : (tensor<32x4xf32>) -> tensor<32x4xf32>
    %2 = stablehlo.transpose %1, dims = [1, 0] : (tensor<32x4xf32>) -> tensor<4x32xf32>
    return %2 : tensor<4x32xf32>
  }
}
"""

_SYNTH_DRIFT = """\
module @jit_step {
  func.func public @main(%arg0: tensor<8x4xf32>) -> tensor<8x2xf32> {
    %c = stablehlo.constant dense<1.000000e+00> : tensor<f64>
    %w = stablehlo.constant dense<0.5> : tensor<4x2xf32>
    %0 = stablehlo.dot_general %arg0, %w, contracting_dims = [1] x [0] : (tensor<8x4xf32>, tensor<4x2xf32>) -> tensor<8x2xf32>
    %1 = stablehlo.transpose %arg0, dims = [0, 2, 3, 1] : (tensor<2x3x8x8xf32>) -> tensor<2x8x8x3xf32>
    return %0 : tensor<8x2xf32>
  }
}
"""


class TestParser:
    def test_inline_collective_bytes_and_groups(self):
        c = parse_stablehlo("t", _SYNTH)
        ag = [x for x in c.collectives if x.kind == "all-gather"]
        assert len(ag) == 1
        assert ag[0].operand_bytes == 8 * 4 * 4
        assert ag[0].result_bytes == 32 * 4 * 4
        assert ag[0].traffic_bytes == 512
        assert ag[0].groups == "[[0, 1, 2, 3]]"
        assert "tensor<8x4xf32>" in ag[0].types

    def test_region_collective_signature_on_closing_line(self):
        """all_reduce carries its reduction as a region — the type
        signature lives on the closing ``})`` line, not the op line."""
        c = parse_stablehlo("t", _SYNTH)
        ar = [x for x in c.collectives if x.kind == "all-reduce"]
        assert len(ar) == 1
        assert ar[0].operand_bytes == 32 * 4 * 4
        assert ar[0].traffic_bytes == 512

    def test_aggregates(self):
        c = parse_stablehlo("t", _SYNTH)
        assert c.collective_bytes == 1024
        assert c.by_kind() == {
            "all-gather": {"ops": 1, "bytes": 512},
            "all-reduce": {"ops": 1, "bytes": 512}}
        assert c.transposes == 1 and c.rank4_transposes == 0
        assert not c.f64_ops and not c.f32_compute_ops

    def test_f64_f32_and_rank4_detection(self):
        c = parse_stablehlo("t", _SYNTH_DRIFT)
        assert len(c.f64_ops) == 1 and "constant" in c.f64_ops[0]
        assert len(c.f32_compute_ops) == 1
        assert c.f32_compute_ops[0].startswith("stablehlo.dot_general")
        assert c.rank4_transposes == 1 and c.transposes == 1
        assert c.collectives == []

    def test_summary_is_json_safe(self):
        s = parse_stablehlo("t", _SYNTH).summary()
        json.dumps(s)
        assert s["label"] == "t" and s["collective_bytes"] == 1024


# ---------------------------------------------------------------------------
# pass families over synthetic programs (conftest arms all three STRICT)
# ---------------------------------------------------------------------------

class TestPasses:
    def test_undeclared_kind_is_a_violation(self):
        contract = StepContract(label="t", collectives=(
            CollectiveBound("all-reduce"),))
        rep = audit_step("t", _SYNTH, contract=contract)
        assert not rep.ok
        v = rep.violations[0]
        assert v.pass_name == "collective"
        assert v.op == "stablehlo.all_gather"
        assert v.step == "t" and "undeclared" in v.detail
        assert rep.strict_violations          # conftest armed strict
        with pytest.raises(ProgramContractError):
            rep.raise_or_warn()

    def test_max_ops_and_max_bytes_budgets(self):
        over_ops = StepContract(label="t", collectives=(
            CollectiveBound("all-gather", max_ops=0),
            CollectiveBound("all-reduce")))
        rep = audit_step("t", _SYNTH, contract=over_ops)
        assert any("exceed the declared max of 0" in v.detail
                   for v in rep.violations)
        over_bytes = StepContract(label="t", collectives=(
            CollectiveBound("all-gather", max_bytes=100),
            CollectiveBound("all-reduce")))
        rep2 = audit_step("t", _SYNTH, contract=over_bytes)
        assert any("512 bytes exceeds the declared budget of 100"
                   in v.detail for v in rep2.violations)

    def test_within_budget_is_clean(self):
        contract = StepContract(label="t", collectives=(
            CollectiveBound("all-gather", max_ops=1, max_bytes=512),
            CollectiveBound("all-reduce", max_ops=1, max_bytes=512)))
        assert audit_step("t", _SYNTH, contract=contract).ok

    def test_f64_flagged_regardless_of_contract(self):
        rep = audit_step("t", _SYNTH_DRIFT,
                         contract=StepContract(label="t"))
        f64 = [v for v in rep.violations if "f64" in v.detail]
        assert f64 and f64[0].pass_name == "precision"

    def test_f32_compute_only_under_declared_bf16(self):
        fp32 = StepContract(label="t", activation_dtype="fp32")
        bf16 = StepContract(label="t", activation_dtype="bf16")
        text = _SYNTH_DRIFT.replace(
            "dense<1.000000e+00> : tensor<f64>",
            "dense<1.000000e+00> : tensor<f32>")   # drop the f64 finding
        assert audit_step("t", text, contract=fp32).ok
        rep = audit_step("t", text, contract=bf16)
        assert len(rep.violations) == 1
        v = rep.violations[0]
        assert v.op == "stablehlo.dot_general" and "bf16" in v.detail

    def test_rank4_transpose_budget(self):
        tight = StepContract(label="t", max_rank4_transposes=0)
        rep = audit_step("t", _SYNTH_DRIFT.replace(
            "tensor<f64>", "tensor<f32>"), contract=tight)
        mem = [v for v in rep.violations if v.pass_name == "memory"]
        assert mem and mem[0].op == "stablehlo.transpose"

    def test_off_mode_disables_pass(self):
        config.set_property("bigdl.audit.collectives", "off")
        try:
            rep = audit_step("t", _SYNTH, contract=StepContract(label="t"))
            assert rep.ok                    # undeclared kinds, pass off
        finally:
            config.set_property("bigdl.audit.collectives", "strict")

    def test_warn_mode_logs_not_raises(self, caplog):
        config.set_property("bigdl.audit.collectives", "warn")
        try:
            rep = audit_step("t", _SYNTH, contract=StepContract(label="t"))
            assert rep.violations and not rep.strict_violations
            import logging
            with caplog.at_level(logging.WARNING, logger="bigdl_tpu"):
                rep.raise_or_warn()          # no raise
            assert any("program audit" in r.message for r in caplog.records)
        finally:
            config.set_property("bigdl.audit.collectives", "strict")

    def test_metrics_exported(self):
        audit_step("metrics_probe", _SYNTH,
                   contract=StepContract(label="metrics_probe",
                                         collectives=(
                                             CollectiveBound("all-gather"),
                                             CollectiveBound("all-reduce"))))
        g = telemetry.gauge("Audit/collective_bytes",
                            labels={"step": "metrics_probe"})
        assert g.value == 1024


# ---------------------------------------------------------------------------
# real fused steps: strict-clean end to end, chaos injections caught
# ---------------------------------------------------------------------------

def _samples(n=64, dim=8, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    return [Sample(rng.normal(size=(dim,)).astype(np.float32),
                   np.int64(i % classes + 1)) for i in range(n)]


def _local_trainer(precision=None, iterations=2):
    m = (nn.Sequential().add(nn.Linear(8, 16)).add(nn.Tanh())
         .add(nn.Linear(16, 3)).add(nn.LogSoftMax()))
    m.reset(jax.random.PRNGKey(7))
    o = Optimizer.create(m, _samples(), nn.ClassNLLCriterion(),
                         batch_size=16)
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    o.set_end_when(optim.max_iteration(iterations))
    if precision:
        o.set_precision(precision)
    return o


def _distri_trainer(iterations=2):
    ds = ShardedDataSet(_samples(), N_DEV).transform(
        SampleToMiniBatch(64, N_DEV))
    m = (nn.Sequential().add(nn.Linear(8, 16)).add(nn.Tanh())
         .add(nn.Linear(16, 3)).add(nn.LogSoftMax()))
    m.reset(jax.random.PRNGKey(7))
    o = Optimizer.create(m, ds, nn.ClassNLLCriterion())
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    o.set_end_when(optim.max_iteration(iterations))
    return o


class TestLiveAudit:
    def test_local_step_audits_clean_strict(self):
        """The whole tier-1 suite runs strict (conftest); this test pins
        the property explicitly: a local fused step compiles under the
        strict auditor without a violation and exports its census."""
        assert config.get_property("bigdl.audit.collectives") == "strict"
        _local_trainer().optimize()
        g = telemetry.gauge("Audit/collective_bytes",
                            labels={"step": "local"})
        assert g.value == 0                  # single-device: no collectives

    def test_shard_map_step_audits_clean_strict(self):
        _distri_trainer().optimize()
        g = telemetry.gauge("Audit/collective_bytes",
                            labels={"step": "shard_map"})
        assert g.value > 0                   # rs + ag + scalar all-reduces

    def test_injected_extra_all_gather_caught(self):
        """Chaos: a redundant (bit-exact) second all-gather in the
        shard_map step must trip the collective contract with a report
        naming the op and the owning step."""
        config.set_property("bigdl.chaos.extraAllGather", "true")
        try:
            with pytest.raises(ProgramContractError) as ei:
                _distri_trainer().optimize()
        finally:
            config.clear_property("bigdl.chaos.extraAllGather")
        msg = str(ei.value)
        assert "stablehlo.all_gather" in msg
        assert "step 'shard_map'" in msg
        # the contract declares exactly one all-gather per overlap bucket
        # (bigdl.parallel.overlapBuckets, default 4) — the redundant extra
        # one overflows that count
        n_buckets = config.get_int("bigdl.parallel.overlapBuckets", 4)
        assert f"exceed the declared max of {n_buckets}" in msg
        assert ei.value.violations           # structured, not just a string
        v = [x for x in ei.value.violations if x.op == "stablehlo.all_gather"]
        assert v and v[0].step == "shard_map"
        assert v[0].pass_name == "collective"

    def test_injected_f32_upcast_in_bf16_program_caught(self):
        """Chaos: a numerically-identity f32 matmul smuggled past the
        module-level checker must trip the precision pass on the lowered
        program of the declared-bf16 local step."""
        config.set_property("bigdl.chaos.f32Upcast", "true")
        try:
            with pytest.raises(ProgramContractError) as ei:
                _local_trainer(precision="bf16").optimize()
        finally:
            config.clear_property("bigdl.chaos.f32Upcast")
        msg = str(ei.value)
        assert "stablehlo.dot_general" in msg
        assert "step 'local'" in msg
        assert "declared activation dtype is bf16" in msg
        v = [x for x in ei.value.violations
             if x.pass_name == "precision"]
        assert v and v[0].step == "local"

    def test_bf16_local_step_audits_clean_without_chaos(self):
        _local_trainer(precision="bf16").optimize()


# ---------------------------------------------------------------------------
# offline mode: persisted cache audit + baselines
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_dir(tmp_path):
    d = str(tmp_path / "ccache")
    config.set_property("bigdl.compile.cacheDir", d)
    yield d
    config.clear_property("bigdl.compile.cacheDir")


class TestOffline:
    def test_manifest_records_census_and_cli_audits_clean(self, cache_dir,
                                                          capsys):
        """Entries stored while the audit is armed carry the census in
        their manifest; the offline CLI replays the contract check over
        them and exits 0 on a clean cache."""
        _local_trainer().optimize()
        manifests = [f for f in os.listdir(cache_dir)
                     if f.endswith(".json")]
        assert manifests
        with open(os.path.join(cache_dir, manifests[0])) as f:
            audit = json.load(f)["audit"]
        assert audit["label"] == "local"
        assert audit["collective_bytes"] == 0
        assert audit["peak_bytes"] is None or audit["peak_bytes"] > 0
        rc = hlo_audit.main([cache_dir])
        out = capsys.readouterr().out
        assert rc == 0
        assert "[local]" in out and "0 problem(s)" in out

    def test_auditor_accepts_what_a_real_child_persisted(self, cold_probe):
        """The offline auditor over a cache that a real trainer process
        wrote (``tests/compile_probe.py``, audit passes at their default
        of warn): no problem, and every signature the child compiled is
        there with its census, under the label of its fused step."""
        cache_dir, cold = cold_probe
        lines, problems = hlo_audit.audit_cache_dir(cache_dir)
        assert problems == []
        audited = [ln for ln in lines if "collective(s)" in ln]
        for step, label in (("train/local", "local"), ("eval", "eval")):
            assert cold["steps"][step]["misses"] >= 1
            assert sum(f"[{label}]" in ln for ln in audited) == \
                cold["steps"][step]["misses"], lines

    @pytest.mark.slow
    def test_probe_census_matches_committed_baselines(self, cold_probe):
        """The probe's persisted census against the repository's
        ``audit_baselines.json``: collective bytes within 1.25x + 1 KiB,
        no more rank-4 transposes, no new collective kind."""
        baselines = hlo_audit.load_baselines(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "audit_baselines.json"))
        assert {"local", "eval"} <= set(baselines)
        _, problems = hlo_audit.audit_cache_dir(cold_probe[0], baselines)
        assert problems == []

    def test_cli_flags_undeclared_kind_in_persisted_entry(self, tmp_path,
                                                          capsys):
        """A hand-written entry whose census carries a collective its
        step contract never declared fails the offline audit."""
        d = tmp_path / "cc"
        d.mkdir()
        (d / "k1.json").write_text(json.dumps({
            "label": "local",
            "audit": {"label": "local",
                      "by_kind": {"all-gather": {"ops": 2, "bytes": 4096}},
                      "collective_bytes": 4096, "transposes": 0,
                      "rank4_transposes": 0, "f64_ops": 0,
                      "f32_compute_ops": 0, "peak_bytes": 1}}))
        (d / "k1.commit").write_text("")
        rc = hlo_audit.main([str(d)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "VIOLATION" in out and "undeclared all-gather" in out
        assert "step 'local'" in out

    def test_cli_flags_persisted_f64(self, tmp_path, capsys):
        d = tmp_path / "cc"
        d.mkdir()
        (d / "k1.json").write_text(json.dumps({
            "label": "eval",
            "audit": {"label": "eval", "by_kind": {},
                      "collective_bytes": 0, "transposes": 0,
                      "rank4_transposes": 0, "f64_ops": 3,
                      "f32_compute_ops": 0, "peak_bytes": 1}}))
        (d / "k1.commit").write_text("")
        assert hlo_audit.main([str(d)]) == 1
        assert "3 f64 op(s)" in capsys.readouterr().out

    def test_entry_without_census_is_skipped_not_failed(self, tmp_path,
                                                        capsys):
        d = tmp_path / "cc"
        d.mkdir()
        (d / "k1.json").write_text(json.dumps({"label": "local"}))
        (d / "k1.commit").write_text("")
        assert hlo_audit.main([str(d)]) == 0
        assert "no census recorded" in capsys.readouterr().out

    def test_unreadable_dir_fails(self, tmp_path):
        assert hlo_audit.main([str(tmp_path / "nope")]) == 1

    def test_baseline_regression_check(self):
        base = {"collective_bytes": 1000, "rank4_transposes": 1,
                "by_kind": {"all-reduce": {"ops": 1, "bytes": 1000}}}
        ok = {"collective_bytes": 1200, "rank4_transposes": 1,
              "by_kind": {"all-reduce": {"ops": 1, "bytes": 1200}}}
        assert check_against_baseline("s", ok, base) == []
        grown = dict(ok, collective_bytes=99999)
        assert any("regressed past 1.25x" in p
                   for p in check_against_baseline("s", grown, base))
        flipped = dict(ok, rank4_transposes=2)
        assert any("transpose census" in p
                   for p in check_against_baseline("s", flipped, base))
        new_kind = dict(ok, by_kind={"all-reduce": {"ops": 1, "bytes": 1},
                                     "all-to-all": {"ops": 1, "bytes": 1}})
        assert any("new collective kind" in p
                   for p in check_against_baseline("s", new_kind, base))

    def test_baselines_wired_through_cli(self, cache_dir, tmp_path,
                                         capsys):
        _local_trainer().optimize()
        bl = tmp_path / "audit_baselines.json"
        bl.write_text(json.dumps({"steps": {"local": {
            "collective_bytes": 0, "rank4_transposes": 0,
            "by_kind": {}}}}))
        assert hlo_audit.main([cache_dir, "--baselines", str(bl)]) == 0
        capsys.readouterr()
        # sabotage the baseline: any rank-4 transpose is now a regression
        bl.write_text(json.dumps({"steps": {"local": {
            "collective_bytes": 0, "rank4_transposes": -1,
            "by_kind": {}}}}))
        assert hlo_audit.main([cache_dir, "--baselines", str(bl)]) == 1
        assert "transpose census" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# contract registry
# ---------------------------------------------------------------------------

class TestContractRegistry:
    def test_all_step_families_have_default_contracts(self):
        for label in ("local", "local_feval", "shard_map", "gspmd",
                      "pipeline", "eval", "eval_sharded"):
            assert program_contracts.lookup(label) is not None, label

    def test_declare_overrides_default(self):
        c = StepContract(label="local", activation_dtype="bf16")
        program_contracts.declare(c)
        try:
            assert program_contracts.lookup("local") is c
        finally:
            program_contracts.reset()
        assert program_contracts.lookup("local") is not c
