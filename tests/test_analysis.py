"""The program contract auditor: parser, pass suite, Trainer hook.

Two altitudes of evidence:

* **Seeded mutations** — a toy StableHLO module (written in the exact
  textual forms jax 0.4.x emits, sampled from a real lowered MF step)
  is deliberately broken one contract at a time — extra psum, un-donated
  table, widened dtype, host callback, missing reconcile psum — and the
  corresponding pass (and ONLY that pass) must report the break. No pass
  is allowed to be vacuous.
* **Real programs** — the MF step program lowered on the 8-device mesh
  must parse non-vacuously (donated args seen, result_info paths seen,
  the 2-collective data plane profiled) and certify clean; the Trainer
  ``audit=`` hook must certify at compile time, report through the
  recorder, and raise in strict mode when the contract is violated.
"""

import dataclasses
import json

import numpy as np
import pytest

from fps_tpu.analysis import (
    Certificate,
    CollectiveBudget,
    ContractViolationError,
    DonationAudit,
    DtypeDriftDetector,
    HloProgram,
    HostTransferDetector,
    ProgramAuditor,
    ProgramContract,
    ReplicaConsistency,
    Violation,
    as_auditor,
    certify,
    collective_profile,
    contract_for_trainer,
    count_collectives,
)
from fps_tpu.analysis.hlo import float_widths, tensor_bytes

# ---------------------------------------------------------------------------
# Toy program: the textual forms are verbatim jax 0.4.x StableHLO (one
# donated table arg -> "[0]['tab']" result, one 2048B gathered pull, one
# 2048B routed push, one scalar metric psum, one singleton-group psum).
# ---------------------------------------------------------------------------

GROUPS_1X8 = "dense<[[0, 1, 2, 3, 4, 5, 6, 7]]> : tensor<1x8xi64>"
GROUPS_8X1 = ("dense<[[0], [1], [2], [3], [4], [5], [6], [7]]> "
              ": tensor<8x1xi64>")

TOY = f'''module @jit_step attributes {{mhlo.num_partitions = 8 : i32}} {{
  func.func public @main(%arg0: tensor<64x8xf32> {{jax.buffer_donor = true, mhlo.sharding = "{{devices=[8,1]<=[8]}}"}}, %arg1: tensor<4x32xi32> {{mhlo.sharding = "{{devices=[1,8]<=[8]}}"}}, %arg2: tensor<4x32xf32> {{mhlo.sharding = "{{devices=[1,8]<=[8]}}"}}) -> (tensor<64x8xf32> {{jax.result_info = "[0]['tab']"}}, tensor<4xf32> {{jax.result_info = "[2]['n']"}}) {{
    %0 = stablehlo.custom_call @Sharding(%arg0) {{backend_config = "", mhlo.sharding = "{{devices=[8,1]<=[8]}}"}} : (tensor<64x8xf32>) -> tensor<64x8xf32>
    %1 = stablehlo.custom_call @SPMDFullToShardShape(%0) {{backend_config = "", mhlo.sharding = "{{manual}}"}} : (tensor<64x8xf32>) -> tensor<8x8xf32>
    %2 = "stablehlo.all_gather"(%1) <{{all_gather_dim = 0 : i64, channel_handle = #stablehlo.channel_handle<handle = 1, type = 1>, replica_groups = {GROUPS_1X8}, use_global_device_ids}}> : (tensor<8x8xf32>) -> tensor<64x8xf32>
    %3 = "stablehlo.all_to_all"(%2) <{{channel_handle = #stablehlo.channel_handle<handle = 2, type = 1>, concat_dimension = 0 : i64, replica_groups = {GROUPS_1X8}, split_count = 8 : i64, split_dimension = 0 : i64}}> : (tensor<8x8x8xf32>) -> tensor<8x8x8xf32>
    %4 = "stablehlo.all_reduce"(%3) <{{channel_handle = #stablehlo.channel_handle<handle = 3, type = 1>, replica_groups = {GROUPS_1X8}, use_global_device_ids}}> ({{
    ^bb0(%arg6: tensor<f32>, %arg7: tensor<f32>):
      %90 = stablehlo.add %arg6, %arg7 : tensor<f32>
      stablehlo.return %90 : tensor<f32>
    }}) : (tensor<f32>) -> tensor<f32>
    %5 = "stablehlo.all_reduce"(%4) <{{channel_handle = #stablehlo.channel_handle<handle = 4, type = 1>, replica_groups = {GROUPS_8X1}, use_global_device_ids}}> ({{
    ^bb0(%arg6: tensor<f32>, %arg7: tensor<f32>):
      %91 = stablehlo.add %arg6, %arg7 : tensor<f32>
      stablehlo.return %91 : tensor<f32>
    }}) : (tensor<f32>) -> tensor<f32>
    %6 = stablehlo.add %2, %2 : tensor<64x8xf32>
    return %6, %arg2 : tensor<64x8xf32>, tensor<4xf32>
  }}
}}
'''

# The reconcile psum (region-carrying all_reduce, 2048B payload on the
# closing line) — inserted by mutations that need a big psum present.
RECONCILE_PSUM = f'''    %7 = "stablehlo.all_reduce"(%6) <{{channel_handle = #stablehlo.channel_handle<handle = 5, type = 1>, replica_groups = {GROUPS_1X8}, use_global_device_ids}}> ({{
    ^bb0(%arg6: tensor<f32>, %arg7: tensor<f32>):
      %92 = stablehlo.add %arg6, %arg7 : tensor<f32>
      stablehlo.return %92 : tensor<f32>
    }}) : (tensor<64x8xf32>) -> tensor<64x8xf32>
'''

MARK = "    %6 = stablehlo.add"

# The base contract the unmutated toy satisfies exactly.
BASE = ProgramContract(
    name="toy", max_collectives=2, max_collective_bytes=4096,
    per_kind_max={"all_gather": 1, "all_to_all": 1},
    donated_tables=True, max_float_bits=32,
)


def _insert(extra: str) -> str:
    assert MARK in TOY
    return TOY.replace(MARK, extra + MARK)


def _pass_names(cert: Certificate) -> set:
    return {v.pass_name for v in cert.violations}


# ---------------------------------------------------------------------------
# Parser.
# ---------------------------------------------------------------------------


def test_tensor_bytes_and_float_widths():
    assert tensor_bytes("(tensor<8x8xf32>) -> tensor<64x8xf32>") == 2048
    assert tensor_bytes("tensor<4xi32>") == 16
    assert tensor_bytes("tensor<f32>") == 0  # scalar: below accounting
    assert float_widths("(tensor<8xbf16>) -> tensor<8xf32>") == [16, 32]
    assert float_widths("tensor<4xf64>") == [64]
    assert float_widths("tensor<4xi32>") == []


def test_toy_parses_ops_args_results():
    prog = HloProgram.from_text(TOY)
    kinds = [op.kind for op in prog.ops]
    assert kinds.count("custom_call") == 2
    assert kinds.count("all_gather") == 1
    assert kinds.count("all_reduce") == 2
    # @main metadata: the donated table arg and both result paths.
    assert len(prog.args) == 3
    assert prog.args[0].donated and not prog.args[1].donated
    assert [r.info for r in prog.results] == ["[0]['tab']", "[2]['n']"]
    # Replica groups parse into id tuples; the 8x1 form is 8 singletons.
    ag = prog.by_kind("all_gather")[0]
    assert ag.replica_groups == ((0, 1, 2, 3, 4, 5, 6, 7),)
    assert ag.group_size == 8
    assert prog.by_kind("all_reduce")[1].group_size == 1


def test_arg_attrs_survive_quoted_braces():
    """mhlo.sharding's quoted value contains '}' — attributes sorted
    after it (tf.aliasing_output, the donation marker some jax versions
    emit instead of jax.buffer_donor) must still be seen; a naive
    [^}]* attr match truncates inside the quote and reports a
    correctly-donated program as un-donated."""
    sig = (
        'func.func public @main('
        '%arg0: tensor<64x8xf32> {mhlo.sharding = '
        '"{devices=[8,1]<=[8]}", tf.aliasing_output = 0 : i32}, '
        '%arg1: tensor<4x32xi32> {mhlo.sharding = '
        '"{devices=[1,8]<=[8]}"}) -> '
        '(tensor<64x8xf32> {mhlo.sharding = "{devices=[8,1]<=[8]}", '
        'jax.result_info = "[0][\'tab\']"}) {'
    )
    args, results = HloProgram._parse_main(sig)
    assert [a.index for a in args] == [0, 1]
    assert args[0].donated and "tf.aliasing_output" in args[0].attrs
    assert not args[1].donated
    # Result attrs after a quoted-brace sharding are also still read.
    assert results[0].info == "[0]['tab']"


def test_main_signature_in_jax_0_9_form():
    """The installed jax (0.9.0) writes ``result[0]['tab']`` where 0.4.x
    wrote ``[0]['tab']`` and shards with sdy attributes whose value nests
    braces; both forms must yield the same bare path, or DonationAudit
    sees no table result and certifies an empty model."""
    sig = (
        'func.func public @main('
        '%arg0: tensor<48x4xf32> {jax.buffer_donor = true, sdy.sharding = '
        '#sdy.sharding<@mesh, [{"shard"}, {}]>}, '
        '%arg1: tensor<4x256xi32> {sdy.sharding = '
        '#sdy.sharding<@mesh, [{}, {"data", "shard"}]>}) -> '
        '(tensor<48x4xf32> {jax.result_info = "result[0][\'tab\']"}, '
        'tensor<64x4xf32> {jax.result_info = "result[1]"}, '
        'tensor<4xf32> {jax.result_info = "result"}) {'
    )
    args, results = HloProgram._parse_main(sig)
    assert [a.donated for a in args] == [True, False]
    assert [r.info for r in results] == ["[0]['tab']", "[1]", ""]


def test_collective_profile_thresholds():
    # 2 data-plane collectives: the scalar psum is sub-threshold, the
    # singleton-group psum is excluded regardless of payload.
    prof = collective_profile(TOY)
    assert [(c.kind, c.payload_bytes) for c in prof] == [
        ("all_gather", 2048), ("all_to_all", 2048)]
    assert count_collectives(TOY) == 2
    # min_bytes=0 admits the scalar psum but still not the singleton.
    assert count_collectives(TOY, min_bytes=0) == 3


def test_region_payload_from_closing_line():
    # The reconcile psum's op line names only the replica-groups
    # constant; its 2048B payload sits on the region's closing line.
    prog = HloProgram.from_text(_insert(RECONCILE_PSUM))
    big = [op for op in prog.by_kind("all_reduce")
           if op.payload_bytes >= 1024]
    assert len(big) == 1 and big[0].payload_bytes == 2048


# ---------------------------------------------------------------------------
# Seeded mutations: each break is caught by exactly the pass that owns it.
# ---------------------------------------------------------------------------


def test_toy_certifies_clean_under_base_contract():
    cert = certify(TOY, BASE, program="toy")
    assert cert.ok, [v.summary for v in cert.violations]
    assert cert.collective_count == 2
    assert cert.collective_bytes == 4096


def test_mutation_extra_psum_breaks_collective_budget():
    cert = certify(_insert(RECONCILE_PSUM), BASE)
    assert not cert.ok
    assert _pass_names(cert) == {"collective_budget"}
    # Both the count (3 > 2) and the byte (6144 > 4096) budgets fire.
    assert len(cert.violations) == 2
    assert cert.collective_count == 3


def test_mutation_per_kind_budget():
    contract = ProgramContract(per_kind_max={"all_gather": 0})
    cert = certify(TOY, contract)
    assert _pass_names(cert) == {"collective_budget"}
    assert "all_gather" in cert.violations[0].summary


def test_mutation_removed_collective_breaks_exact_budget():
    """Pinned-exact budgets (the audit tool's re-pinning workflow) fail
    on a REMOVED collective too, where a plain ceiling is blind."""
    mutated = "\n".join(l for l in TOY.splitlines()
                        if "all_to_all" not in l)
    exact = dataclasses.replace(BASE, exact_collectives=True)
    cert = certify(mutated, exact, program="mutant")
    assert not cert.ok
    assert _pass_names(cert) == {"collective_budget"}
    # Total count (1 != 2) and the all_to_all per-kind pin (0 < 1).
    assert any("differ from the pinned budget" in v.summary
               for v in cert.violations)
    assert any("fall short of the pinned per-kind" in v.summary
               for v in cert.violations)
    # The ceiling form of the same contract passes the mutant: exactly
    # the gap exact_collectives closes.
    assert certify(mutated, BASE, program="mutant").ok
    # And the unmutated program still certifies clean under exact pins.
    assert certify(TOY, exact, program="clean").ok


def test_mutation_unpinned_kind_breaks_exact_budget():
    """Under exact pins a NEW collective kind fails even when the total
    count cap alone would admit it."""
    mutated = TOY.replace('"stablehlo.all_to_all"',
                          '"stablehlo.collective_permute"')
    exact = dataclasses.replace(BASE, exact_collectives=True)
    cert = certify(mutated, exact, program="mutant")
    assert not cert.ok
    assert any("not in the pinned per-kind budget" in v.summary
               for v in cert.violations)


def test_mutation_undonate_breaks_donation():
    cert = certify(TOY.replace("jax.buffer_donor = true, ", ""), BASE)
    assert not cert.ok
    assert _pass_names(cert) == {"donation"}
    assert "'tab'" in cert.violations[0].summary


def test_mutation_widening_convert_breaks_dtype_drift():
    extra = ("    %9 = stablehlo.convert %2 : (tensor<64x8xbf16>) -> "
             "tensor<64x8xf32>\n")
    cert = certify(_insert(extra), BASE)
    assert not cert.ok
    assert _pass_names(cert) == {"dtype_drift"}
    assert "f16->f32" in cert.violations[0].summary


def test_mutation_f64_op_breaks_dtype_drift():
    extra = "    %9 = stablehlo.add %2, %2 : tensor<64x8xf64>\n"
    cert = certify(_insert(extra), BASE)
    assert not cert.ok
    assert _pass_names(cert) == {"dtype_drift"}
    assert "wider than f32" in cert.violations[0].summary


def test_mutation_host_callback_breaks_host_transfer():
    extra = ('    %9 = stablehlo.custom_call @xla_python_cpu_callback(%2) '
             '{api_version = 2 : i32} : (tensor<64x8xf32>) -> '
             'tensor<64x8xf32>\n')
    cert = certify(_insert(extra), BASE)
    assert not cert.ok
    assert _pass_names(cert) == {"host_transfer"}
    assert "xla_python_cpu_callback" in cert.violations[0].summary
    # The same callback certifies clean when the contract declares it.
    import dataclasses

    allowed = dataclasses.replace(
        BASE, allow_host_transfers=("xla_python_cpu_callback",))
    assert certify(_insert(extra), allowed).ok


def test_mutation_infeed_breaks_host_transfer():
    extra = ('    %9 = "stablehlo.infeed"(%2) : (!stablehlo.token) -> '
             '(tensor<4xf32>, !stablehlo.token)\n')
    cert = certify(_insert(extra), BASE)
    assert _pass_names(cert) == {"host_transfer"}
    assert "infeed" in cert.violations[0].summary


def test_mutation_missing_reconcile_psum_breaks_replica_consistency():
    import dataclasses

    tiered = dataclasses.replace(
        BASE, require_shard_psum=True, hot_reconcile_bytes=1024,
        shard_group_size=8)
    # The plain toy claims tiering but has no big shard-axis psum.
    cert = certify(TOY, tiered)
    assert not cert.ok
    assert _pass_names(cert) == {"replica_consistency"}
    # With the reconcile psum present the SAME contract certifies —
    # modulo the count budget the extra op now exceeds, which is
    # collective_budget's finding, not replica_consistency's.
    tiered3 = dataclasses.replace(
        tiered, max_collectives=3, max_collective_bytes=8192,
        per_kind_max={"all_gather": 1, "all_to_all": 1, "all_reduce": 1})
    assert certify(_insert(RECONCILE_PSUM), tiered3).ok
    # A psum on the WRONG axis (singleton groups) does not satisfy it:
    # the toy's 8x1 psum is group_size 1.
    assert not certify(TOY, tiered).ok


RECONCILE_RS = f'''    %7 = "stablehlo.reduce_scatter"(%6) <{{channel_handle = #stablehlo.channel_handle<handle = 5, type = 1>, replica_groups = {GROUPS_1X8}, scatter_dimension = 0 : i64, use_global_device_ids}}> ({{
    ^bb0(%arg6: tensor<f32>, %arg7: tensor<f32>):
      %92 = stablehlo.add %arg6, %arg7 : tensor<f32>
      stablehlo.return %92 : tensor<f32>
    }}) : (tensor<64x8xf32>) -> tensor<8x8xf32>
'''


def test_sharded_reconcile_rs_satisfies_replica_consistency():
    """PR 10: the window reconcile lowers a reduce-scatter (each replica
    applies its 1/S slice) — ReplicaConsistency accepts it in place of
    the legacy full-head psum, with the same group-size and payload
    gates."""
    import dataclasses

    tiered = dataclasses.replace(
        BASE, require_shard_psum=True, hot_reconcile_bytes=1024,
        shard_group_size=8, max_collectives=3,
        max_collective_bytes=8192,
        per_kind_max={"all_gather": 1, "all_to_all": 1,
                      "reduce_scatter": 1})
    assert certify(_insert(RECONCILE_RS), tiered).ok
    # An undersized reduce_scatter does not satisfy the reconcile bound.
    small = dataclasses.replace(tiered, hot_reconcile_bytes=1 << 20)
    cert = certify(_insert(RECONCILE_RS), small)
    assert not cert.ok
    assert "replica_consistency" in _pass_names(cert)


def test_audit_diff_budgets_gate():
    """tools/audit_programs.py --diff: growth vs the reference audit
    fails iff it is NOT covered by the current pinned budget (an
    unpinned regression); re-pinned growth and shrinkage pass."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "_audit_programs", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "audit_programs.py"))
    ap = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ap)

    old = {"audit_programs": {
        "mf": {"collectives": {"count": 2, "bytes": 4096}},
        "mf_tiered": {"collectives": {"count": 3, "bytes": 5120}},
        "ghost": {"collectives": {"count": 1, "bytes": 64}},
    }}
    pinned_mf = ap.BUDGETS["mf"]
    # Unchanged + shrunk: clean.
    assert ap.diff_budgets(old, {
        "mf": {"collective_count": 2, "collective_bytes": 4096},
    }) == []
    assert ap.diff_budgets(old, {
        "mf": {"collective_count": 1, "collective_bytes": 2048},
    }) == []
    # Growth covered by the CURRENT pin (mf_tiered was deliberately
    # re-pinned this PR to its sharded-reconcile census): passes.
    cur = {"mf_tiered": {
        "collective_count": ap.BUDGETS["mf_tiered"]["max_collectives"],
        "collective_bytes":
            ap.BUDGETS["mf_tiered"]["max_collective_bytes"]}}
    assert ap.diff_budgets(old, cur) == []
    # Unpinned growth: fails, naming the program.
    bad = {"mf": {"collective_count": pinned_mf["max_collectives"] + 1,
                  "collective_bytes": 999999}}
    problems = ap.diff_budgets(old, bad)
    assert len(problems) == 1 and problems[0].startswith("mf:")
    # Programs absent from the old audit (new rows) never regress.
    assert ap.diff_budgets(old, {
        "brand_new": {"collective_count": 99,
                      "collective_bytes": 1 << 30}}) == []


def test_every_default_pass_has_a_mutation():
    """Meta-test: the suite above covers every registered pass."""
    from fps_tpu.analysis import DEFAULT_PASSES

    assert {p.name for p in DEFAULT_PASSES} == {
        "collective_budget", "host_transfer", "donation", "dtype_drift",
        "replica_consistency"}
    assert {type(p) for p in DEFAULT_PASSES} == {
        CollectiveBudget, HostTransferDetector, DonationAudit,
        DtypeDriftDetector, ReplicaConsistency}


# ---------------------------------------------------------------------------
# Certificates, auditor, normalization.
# ---------------------------------------------------------------------------


def test_certificate_json_roundtrip():
    cert = certify(TOY, BASE, program="toy")
    doc = cert.to_json()
    assert doc["ok"] is True and doc["program"] == "toy"
    assert doc["collectives"]["count"] == 2
    assert doc["collectives"]["per_kind"]["all_gather"]["bytes"] == 2048
    assert doc["contract"]["max_collectives"] == 2
    json.dumps(doc)  # must be serializable as-is


def test_violation_json():
    v = Violation(pass_name="donation", summary="s", op_kind="", line=3)
    assert v.to_json() == {"pass_name": "donation", "summary": "s",
                           "op_kind": "", "line": 3}


class _FakeRecorder:
    def __init__(self):
        self.incs, self.events = [], []

    def inc(self, name, value=1.0, **labels):
        self.incs.append((name, value, labels))

    def event(self, etype, **fields):
        self.events.append((etype, fields))


def test_auditor_records_certified_and_violations():
    rec = _FakeRecorder()
    auditor = ProgramAuditor(contract=BASE, recorder=rec)
    cert = auditor.certify("toy/clean", TOY)
    assert cert.ok
    assert ("analysis.certified_programs", 1.0, {}) in rec.incs
    bad = auditor.certify("toy/bad", _insert(RECONCILE_PSUM))
    assert not bad.ok
    rules = [labels["rule"] for name, _, labels in rec.incs
             if name == "analysis.contract_violations"]
    assert rules == ["collective_budget", "collective_budget"]
    etypes = [e for e, _ in rec.events]
    assert etypes == ["analysis.contract_violation"] * 2
    assert rec.events[0][1]["program"] == "toy/bad"
    assert auditor.certificates == [cert, bad]


def test_auditor_strict_raises_with_certificate():
    auditor = ProgramAuditor(contract=BASE, strict=True,
                             recorder=_FakeRecorder())
    with pytest.raises(ContractViolationError) as ei:
        auditor.certify("toy/bad", _insert(RECONCILE_PSUM))
    assert ei.value.certificate.program == "toy/bad"
    assert "collective_budget" in str(ei.value)


def test_as_auditor_normalization():
    auditor = ProgramAuditor()
    assert as_auditor(auditor) is auditor
    assert as_auditor(BASE).contract is BASE
    assert as_auditor(True).strict is False
    assert as_auditor("strict").strict is True
    # None and False mean disabled, so boolean flags wire straight
    # through Trainer(audit=...).
    assert as_auditor(None) is None
    assert as_auditor(False) is None
    with pytest.raises(TypeError):
        as_auditor(17)


# ---------------------------------------------------------------------------
# Real programs: the Trainer hook and contract_for_trainer.
# ---------------------------------------------------------------------------

NU, NI, RANK = 96, 64, 4


def _mf_run(mesh, *, audit=None, chunks_n=2):
    import jax

    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import multi_epoch_chunks
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.utils.datasets import synthetic_ratings

    cfg = MFConfig(num_users=NU, num_items=NI, rank=RANK)
    trainer, store = online_mf(mesh, cfg)
    trainer.audit = audit
    data = synthetic_ratings(NU, NI, 1500, rank=3, seed=3)
    chunks = list(multi_epoch_chunks(
        data, 1, num_workers=num_workers_of(mesh), local_batch=32,
        steps_per_chunk=4, route_key="user", seed=11))[:chunks_n]
    tables, ls = trainer.init_state(jax.random.key(0))
    tables, ls, m = trainer.fit_stream(tables, ls, iter(chunks),
                                       jax.random.key(1))
    return trainer, store, m


@pytest.fixture(scope="module")
def mf_hlo(devices8):
    """One lowered MF step program on the 8-device mesh."""
    import jax

    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import multi_epoch_chunks
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import make_ps_mesh
    from fps_tpu.utils.datasets import synthetic_ratings

    mesh = make_ps_mesh(num_shards=8, num_data=1)
    trainer, _ = online_mf(mesh, MFConfig(num_users=NU, num_items=NI,
                                          rank=RANK))
    data = synthetic_ratings(NU, NI, 1500, rank=3, seed=3)
    chunk = next(iter(multi_epoch_chunks(
        data, 1, num_workers=num_workers_of(mesh), local_batch=32,
        steps_per_chunk=4, route_key="user", seed=11)))
    placed = trainer._place_chunk(chunk)
    tables, ls = trainer.init_state(jax.random.key(0))
    fn = trainer._get_compiled("sync")
    return trainer, fn.lower(tables, ls, placed,
                             jax.random.key(1)).as_text()


def test_real_mf_program_parses_nonvacuously(mf_hlo):
    """Guard against parser rot: if a jax upgrade changes the textual
    form, these assertions fail loudly instead of every pass silently
    passing on an empty model."""
    _, hlo = mf_hlo
    prog = HloProgram.from_text(hlo)
    assert len(prog.ops) > 50
    assert sum(a.donated for a in prog.args) >= 1
    assert any(r.info.startswith("[0]") for r in prog.results)
    # The untiered MF data plane: one gathered pull + one routed push.
    assert [c.kind for c in prog.profile()] == ["all_gather", "all_to_all"]


def test_real_mf_program_certifies_clean(mf_hlo):
    trainer, hlo = mf_hlo
    cert = certify(hlo, contract_for_trainer(trainer, "sync"),
                   program="mf/sync")
    assert cert.ok, [v.summary for v in cert.violations]


def test_contract_for_trainer_untiered(mf_hlo):
    trainer, _ = mf_hlo
    c = contract_for_trainer(trainer, "sync")
    assert c.donated_tables is True
    assert c.max_float_bits == 32
    assert c.require_shard_psum is False and c.shard_group_size is None


def test_contract_for_trainer_tiered(devices8):
    import dataclasses

    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import make_ps_mesh

    mesh = make_ps_mesh(num_shards=8, num_data=1)
    trainer, store = online_mf(mesh, MFConfig(num_users=NU, num_items=NI,
                                              rank=RANK))
    store.specs["item_factors"] = dataclasses.replace(
        store.specs["item_factors"], hot_tier=32)
    trainer.config = dataclasses.replace(trainer.config, hot_sync_every=2)
    c = contract_for_trainer(trainer, "sync")
    assert c.require_shard_psum is True
    assert c.hot_reconcile_bytes == 32 * RANK * 4
    assert c.shard_group_size == 8


def test_trainer_audit_certifies_at_compile_time(devices8):
    from fps_tpu.parallel.mesh import make_ps_mesh

    mesh = make_ps_mesh(num_shards=8, num_data=1)
    trainer, _, m = _mf_run(mesh, audit=True)
    auditor = trainer.audit
    assert isinstance(auditor, ProgramAuditor)
    # One program compiled for the whole stream -> exactly one
    # certificate, clean under the derived contract.
    assert [c.program for c in auditor.certificates] == ["chunk/sync"]
    assert auditor.certificates[0].ok
    assert len(m) == 2  # the run itself was untouched


def test_trainer_audit_reports_violations_through_recorder(devices8):
    from fps_tpu.parallel.mesh import make_ps_mesh

    mesh = make_ps_mesh(num_shards=8, num_data=1)
    rec = _FakeRecorder()
    impossible = ProgramContract(name="impossible", max_collectives=0)
    trainer, _, _ = _mf_run(mesh, audit=ProgramAuditor(
        contract=impossible, recorder=rec))
    assert not trainer.audit.certificates[0].ok
    assert any(n == "analysis.contract_violations" for n, _, _ in rec.incs)
    assert rec.events and rec.events[0][0] == "analysis.contract_violation"


def test_trainer_audit_strict_raises(devices8):
    from fps_tpu.parallel.mesh import make_ps_mesh

    mesh = make_ps_mesh(num_shards=8, num_data=1)
    impossible = ProgramContract(name="impossible", max_collectives=0)
    with pytest.raises(ContractViolationError):
        _mf_run(mesh, audit=ProgramAuditor(contract=impossible,
                                           strict=True))


def test_trainer_audit_off_is_passthrough(devices8):
    from fps_tpu.parallel.mesh import make_ps_mesh

    mesh = make_ps_mesh(num_shards=8, num_data=1)
    trainer, _, _ = _mf_run(mesh, audit=None)
    assert trainer.audit is None
    # The cached compiled fn is the bare jitted callable (no wrapper).
    (fn,) = trainer._compiled.values()
    assert not getattr(fn, "_fps_audited", False)


def test_trainer_audit_numerics_unchanged(devices8):
    """Certification is host-side only: the audited run's tables are
    bit-identical to the unaudited run's."""
    from fps_tpu.parallel.mesh import make_ps_mesh

    mesh = make_ps_mesh(num_shards=8, num_data=1)
    _, store_a, _ = _mf_run(mesh, audit=True)
    _, store_b, _ = _mf_run(mesh, audit=None)
    a = np.asarray(store_a.tables["item_factors"])
    b = np.asarray(store_b.tables["item_factors"])
    assert np.array_equal(a, b)


def test_trainer_audit_false_disables(devices8):
    """A boolean flag wired straight through: audit=False at
    construction normalizes to None; assigned after construction it
    still certifies nothing (and doesn't die on the first dispatch)."""
    from fps_tpu.core.driver import Trainer
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import make_ps_mesh

    mesh = make_ps_mesh(num_shards=8, num_data=1)
    trainer, _ = online_mf(mesh, MFConfig(num_users=NU, num_items=NI,
                                          rank=RANK))
    assert Trainer(mesh, trainer.store, trainer.logic,
                   trainer.server_logic, config=trainer.config,
                   audit=False).audit is None
    # Late assignment bypasses ctor normalization; the run must still
    # complete with nothing certified.
    trainer2, _, m = _mf_run(mesh, audit=False)
    assert len(m) == 2
    assert not isinstance(trainer2.audit, ProgramAuditor)


def test_trainer_audit_bad_value_fails_at_construction(devices8):
    """A typo'd audit= value raises at Trainer construction, not on the
    first compiled dispatch mid-run."""
    from fps_tpu.core.driver import Trainer
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import make_ps_mesh

    mesh = make_ps_mesh(num_shards=8, num_data=1)
    trainer, _ = online_mf(mesh, MFConfig(num_users=NU, num_items=NI,
                                          rank=RANK))
    with pytest.raises(TypeError, match="audit"):
        Trainer(mesh, trainer.store, trainer.logic, trainer.server_logic,
                config=trainer.config, audit="strictt")


def test_lowered_chunk_text_is_certifiable(devices8):
    """Trainer.lowered_chunk_text — the shared entry the analysis tools
    (audit_programs, chaos_sweep's certificate, bench's tiered A/B)
    lower through — produces the dispatched program: parses
    non-vacuously and certifies clean under the trainer's own derived
    contract."""
    import jax

    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import multi_epoch_chunks
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import make_ps_mesh
    from fps_tpu.utils.datasets import synthetic_ratings

    mesh = make_ps_mesh(num_shards=8, num_data=1)
    trainer, _ = online_mf(mesh, MFConfig(num_users=NU, num_items=NI,
                                          rank=RANK))
    data = synthetic_ratings(NU, NI, 1500, rank=3, seed=3)
    chunk = next(iter(multi_epoch_chunks(
        data, 1, num_workers=num_workers_of(mesh), local_batch=32,
        steps_per_chunk=4, route_key="user", seed=11)))
    text = trainer.lowered_chunk_text(chunk)
    prog = HloProgram.from_text(text)
    assert len(prog.ops) > 50 and any(a.donated for a in prog.args)
    assert collective_profile(text)
    cert = certify(text, contract_for_trainer(trainer, "sync"),
                   program="helper/sync")
    assert cert.ok, cert.violations
    # Read-only on the trainer: certifying AFTER a run (chaos_sweep's
    # order is run -> certificate -> read the store) must not clobber
    # the trained weights store.init writes in place.
    tables, ls = trainer.init_state(jax.random.key(0))
    tables, ls, _ = trainer.fit_stream(tables, ls, iter([chunk]),
                                       jax.random.key(1))
    trained = {k: np.asarray(v) for k, v in trainer.store.tables.items()}
    trainer.lowered_chunk_text(chunk)
    for k, v in trained.items():
        assert np.array_equal(np.asarray(trainer.store.tables[k]), v), k


def test_audit_programs_offline_hlo_is_jax_free(tmp_path):
    """tools/audit_programs.py --hlo profiles a saved dump with jax
    unimportable — the login-node workflow the analysis docstrings
    promise (jax is poisoned in sys.modules, so any import attempt
    raises)."""
    import os
    import subprocess
    import sys

    dump = tmp_path / "toy.hlo.txt"
    dump.write_text(TOY)
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "audit_programs.py")
    code = (
        "import sys, runpy\n"
        "sys.modules['jax'] = None\n"
        f"sys.argv = ['audit_programs.py', '--hlo', {str(dump)!r}]\n"
        f"runpy.run_path({tool!r}, run_name='__main__')\n"
    )
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout)
    entry = out[str(dump)]
    assert entry["collectives"] == 2
    assert entry["bytes"] == 4096
    assert {p["kind"] for p in entry["profile"]} == {"all_gather",
                                                     "all_to_all"}


@pytest.mark.slow
def test_audit_programs_importable_without_reexec():
    """Importing the module (to reuse BUDGETS/builders) must not
    execve-replace the importing process — only the CLI re-execs."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys; sys.path.insert(0, 'tools'); "
            "import audit_programs; "
            "print('IMPORT_OK', len(audit_programs.BUDGETS))")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr
    # 11 pinned rows (mf_megastep joined the PR-10 census of 10 when
    # the fused dispatch got its own budget).
    assert "IMPORT_OK 11" in proc.stdout
