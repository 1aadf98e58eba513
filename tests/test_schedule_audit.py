"""Schedule-graph auditor: HLO DAG parsing, cost/critical-path model,
serialized/overlappable classification, contracts, and the StepSchedule
declaration check.

Two layers:

* handwritten-HLO units for the operand extraction the PR 7 census
  never needed — through fusions (``calls=``), tuple-shaped operands,
  while-lowered scatters (``body=``/``condition=``), and the
  post-layout TPU shape spellings (``{1,0:T(8,128)}`` — the PR 7
  regression class) — plus cycle-detection and root-finding sanity;
* the real compiled hybrid step on the 8-virtual-device CPU mesh: the
  id / out / grad all-to-alls report as SERIALIZED on the critical path
  (the documented baseline), and a seeded overlap-declaring
  StepSchedule against the serialized program fails.
"""

import json

import numpy as np
import optax
import pytest

from distributed_embeddings_tpu.analysis import schedule_audit as sa
from distributed_embeddings_tpu.parallel import SparseAdagrad
from distributed_embeddings_tpu.parallel.schedule import (
    PHASE_APPLY, PHASE_DENSE, PHASE_GRAD_EXCHANGE, PHASE_ID_EXCHANGE,
    PHASE_LOOKUP, PHASE_OUT_EXCHANGE, PhaseDecl, ScheduleError,
    StepSchedule, default_schedule)

# --------------------------------------------------- handwritten modules

HLO_FUSION_TUPLE = """\
HloModule test, entry_computation_layout={(f32[16,8])->f32[16,8]}

%fused_computation (param_0: f32[16,8]) -> f32[16,8] {
  %param_0 = f32[16,8]{1,0} parameter(0)
  ROOT %neg = f32[16,8]{1,0} negate(f32[16,8]{1,0} %param_0), metadata={op_name="jit(f)/detpu/lookup_w8_d/neg"}
}

ENTRY %main (p0: f32[16,8], p1: s32[4]) -> f32[16,8] {
  %p0 = f32[16,8]{1,0:T(8,128)} parameter(0)
  %p1 = s32[4]{0} parameter(1)
  %fusion = f32[16,8]{1,0} fusion(f32[16,8]{1,0:T(8,128)} %p0), kind=kLoop, calls=%fused_computation
  %tup = (f32[16,8]{1,0}, s32[4]{0}) tuple(f32[16,8]{1,0} %fusion, s32[4]{0} %p1)
  %gte = f32[16,8]{1,0} get-tuple-element((f32[16,8]{1,0}, s32[4]{0}) %tup), index=0
  ROOT %add = f32[16,8]{1,0} add(f32[16,8]{1,0} %gte, f32[16,8]{1,0} %fusion), metadata={op_name="jit(f)/detpu/lookup_w8_d/add"}
}
"""

HLO_WHILE_SCATTER = """\
HloModule scat

%wbody (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p = (s32[], f32[8,4]{1,0}) parameter(0), metadata={op_name="jit(f)/detpu/sparse_apply/detpu/sparse_apply_w4/scatter-add"}
  %i = s32[] get-tuple-element((s32[], f32[8,4]{1,0}) %p), index=0
  %buf = f32[8,4]{1,0} get-tuple-element((s32[], f32[8,4]{1,0}) %p), index=1
  ROOT %out = (s32[], f32[8,4]{1,0}) tuple(s32[] %i, f32[8,4]{1,0} %buf)
}

%wcond (p: (s32[], f32[8,4])) -> pred[] {
  %p = (s32[], f32[8,4]{1,0}) parameter(0)
  %i2 = s32[] get-tuple-element((s32[], f32[8,4]{1,0}) %p), index=0
  %n = s32[] constant(8)
  ROOT %lt = pred[] compare(s32[] %i2, s32[] %n), direction=LT
}

ENTRY %main (a: f32[8,4]) -> f32[8,4] {
  %a = f32[8,4]{1,0:T(8,128)S(1)} parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8,4]{1,0}) tuple(s32[] %z, f32[8,4]{1,0} %a)
  %w = (s32[], f32[8,4]{1,0}) while((s32[], f32[8,4]{1,0}) %init), condition=%wcond, body=%wbody, backend_config={"known_trip_count":{"n":"8"}}
  ROOT %res = f32[8,4]{1,0} get-tuple-element((s32[], f32[8,4]{1,0}) %w), index=1
}
"""

HLO_CYCLE = """\
HloModule cyc

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %a = f32[4]{0} add(f32[4]{0} %p, f32[4]{0} %b)
  ROOT %b = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %p)
}
"""


def _overlap_module(world_payload_cols: int, with_big_compute: bool) -> str:
    """An all-reduce plus (optionally) a big INDEPENDENT multiply: the
    classification fixture."""
    big = (
        '  %big = f32[1000,100]{1,0} multiply(f32[1000,100]{1,0} %q, '
        'f32[1000,100]{1,0} %q), metadata={op_name="jit(f)/detpu/'
        'dense_forward_backward/mul"}\n')
    consume = "f32[1000,100]{1,0} %big" if with_big_compute \
        else "f32[1000,100]{1,0} %q"
    return (
        "HloModule ov\n\n"
        "ENTRY %main (p: f32[64], q: f32[1000,100]) -> "
        "(f32[64], f32[1000,100]) {\n"
        "  %p = f32[64]{0} parameter(0)\n"
        "  %q = f32[1000,100]{1,0} parameter(1)\n"
        f"  %coll = f32[{world_payload_cols}]{{0}} all-reduce("
        f"f32[{world_payload_cols}]{{0}} %p), "
        'metadata={op_name="jit(f)/detpu/id_all_to_all/all_reduce"}\n'
        + (big if with_big_compute else "")
        + f"  ROOT %t = (f32[64]{{0}}, f32[1000,100]{{1,0}}) tuple("
        f"f32[64]{{0}} %coll, {consume})\n"
        "}\n")


# ------------------------------------------------------------ parser units


def test_operand_extraction_through_fusion_and_tuples():
    comps = sa.parse_hlo_module(HLO_FUSION_TUPLE)
    entry = sa.entry_computation(comps)
    by = entry.by_name()
    assert by["fusion"].operands == ("p0",)
    assert by["fusion"].called == ("fused_computation",)
    # tuple-shaped operand: the gte consumes the 2-element tuple
    assert by["gte"].operands == ("tup",)
    assert by["tup"].operands == ("fusion", "p1")
    # two operands, one repeated name each resolves
    assert by["add"].operands == ("gte", "fusion")
    assert by["add"].is_root
    # the non-entry computation parsed too (phase resolution reads it)
    assert "fused_computation" in comps
    # post-layout TPU tile spelling did not break shape/operand parsing
    assert "T(8,128)" in by["p0"].shape


def test_fusion_phase_falls_back_to_called_computation():
    comps = sa.parse_hlo_module(HLO_FUSION_TUPLE)
    entry = sa.entry_computation(comps)
    fusion = entry.by_name()["fusion"]
    assert fusion.phase == ""  # no op_name on the fusion instruction
    assert sa._resolve_phase(fusion, comps) == "lookup_w8_d"


def test_while_lowered_scatter_parses_and_attributes():
    comps = sa.parse_hlo_module(HLO_WHILE_SCATTER)
    entry = sa.entry_computation(comps)
    by = entry.by_name()
    w = by["w"]
    assert w.op == "while"
    assert w.operands == ("init",)
    assert set(w.called) == {"wcond", "wbody"}
    # no op_name on the while itself: phase resolves from the BODY's
    # scatter-add scope (majority vote over called computations)
    assert sa._resolve_phase(w, comps) == "sparse_apply/sparse_apply_w4"
    # S(1) memory-space spelling parsed
    assert "S(1)" in by["a"].shape
    g = sa.ScheduleGraph(comps, world=1)
    # while consumes init which consumes the param: a real chain
    order = g.topo_order()
    idx = {g.nodes[i].instr.name: order.index(i)
           for i in range(len(g.nodes))}
    assert idx["init"] < idx["w"] < idx["res"]


def test_cycle_detection_raises():
    g = sa.ScheduleGraph(sa.parse_hlo_module(HLO_CYCLE), world=1)
    with pytest.raises(sa.ScheduleGraphError, match="cycle"):
        g.topo_order()


def test_root_finding_sanity():
    g = sa.ScheduleGraph(sa.parse_hlo_module(HLO_FUSION_TUPLE), world=1)
    roots = g.roots()
    names = {g.nodes[i].instr.name for i in roots}
    assert "add" in names  # the ROOT instruction is a sink
    # every non-sink feeds something
    assert all(g.succs[i] == [] for i in roots)


def test_audit_text_rejects_garbage():
    with pytest.raises(sa.ScheduleGraphError):
        sa.audit_text("not hlo at all")


# ------------------------------------------------- cost + classification


def test_collective_payload_uses_off_chip_fraction():
    g = sa.ScheduleGraph(sa.parse_hlo_module(
        _overlap_module(64, False)), world=8)
    coll = next(n for n in g.nodes if n.is_collective)
    # operand f32[64] = 256 B; off-chip 7/8 -> 224 B
    assert coll.payload_bytes == 224
    assert coll.cost_ns == pytest.approx(
        224 / sa.CHIP_SPECS["v5e"].ici_eff_gbps)
    # world=1: nothing leaves the chip
    g1 = sa.ScheduleGraph(sa.parse_hlo_module(
        _overlap_module(64, False)), world=1)
    assert next(n for n in g1.nodes if n.is_collective).payload_bytes == 0


def test_classification_overlappable_vs_serialized():
    rep = sa.audit_text(_overlap_module(64, True), world=8)
    (c,) = rep.collectives
    # the big multiply is independent of the all-reduce: overlappable
    assert c.classification == "overlappable"
    assert c.independent_compute_ns > c.cost_ns
    assert rep.serialized_collective_fraction == 0.0

    rep2 = sa.audit_text(_overlap_module(64, False), world=8)
    (c2,) = rep2.collectives
    # nothing independent (parameters are trivial): serialized
    assert c2.classification == "serialized"
    assert c2.independent_compute_ns == 0.0
    assert rep2.serialized_collective_fraction == 1.0


def test_trivial_ops_cost_nothing():
    g = sa.ScheduleGraph(sa.parse_hlo_module(HLO_FUSION_TUPLE), world=1)
    by = {n.instr.name: n for n in g.nodes}
    assert by["p0"].cost_ns == 0.0 and by["p0"].is_trivial
    assert by["tup"].cost_ns == 0.0
    assert by["add"].cost_ns > 0.0


def test_critical_path_longest_chain():
    rep = sa.audit_text(_overlap_module(64, True), world=8)
    # the heaviest chain is q -> big -> tuple, not the tiny collective
    phases = [p for p, _ in rep.critical_path_phases]
    assert any("dense_forward_backward" in p for p in phases)
    assert rep.critical_path_ns > 0
    assert rep.critical_path_bytes > 0


# ----------------------------------------------------- contracts + report


def test_contract_expect_validated():
    with pytest.raises(ValueError, match="expect"):
        sa.ScheduleContract("x", expect="maybe")


def test_contracts_fire_on_mismatch_and_absence():
    rep = sa.audit_text(_overlap_module(64, True), world=8)
    rep.check([sa.ScheduleContract("id_all_to_all",
                                   expect="serialized")])
    assert any("is overlappable, expected serialized" in v
               for v in rep.violations)
    rep2 = sa.audit_text(_overlap_module(64, True), world=8)
    rep2.check([sa.ScheduleContract("no_such_phase")])
    assert any("expected >= 1" in v for v in rep2.violations)
    rep3 = sa.audit_text(_overlap_module(64, True), world=8)
    rep3.check([sa.ScheduleContract("id_all_to_all",
                                    expect="overlappable")])
    assert rep3.ok


def test_report_json_and_markdown_roundtrip():
    rep = sa.audit_text(_overlap_module(64, True), world=8)
    d = json.loads(json.dumps(rep.to_json()))
    assert d["serialized_collective_fraction"] == 0.0
    assert d["collectives"][0]["classification"] == "overlappable"
    md = rep.markdown()
    assert "overlappable" in md and "critical path" in md
    s = rep.summary()
    assert set(s) >= {"serialized_collective_fraction",
                      "critical_path_bytes", "violations"}


# -------------------------------------------------- StepSchedule semantics


def test_default_schedule_validates_and_is_serialized():
    sched = default_schedule()
    assert [p.name for p in sched.collectives()] == [
        PHASE_ID_EXCHANGE, PHASE_OUT_EXCHANGE, PHASE_GRAD_EXCHANGE]
    assert sched.declared_overlaps() == ()
    assert sched.depends_on(PHASE_APPLY, PHASE_ID_EXCHANGE)


def test_schedule_rejects_duplicates_undeclared_cycles_and_self_overlap():
    with pytest.raises(ScheduleError, match="duplicate"):
        StepSchedule("d", (PhaseDecl("a"), PhaseDecl("a")))
    with pytest.raises(ScheduleError, match="undeclared"):
        StepSchedule("d", (PhaseDecl("a", after=("ghost",)),))
    with pytest.raises(ScheduleError, match="cycle"):
        StepSchedule("d", (PhaseDecl("a", after=("b",)),
                           PhaseDecl("b", after=("a",))))
    with pytest.raises(ScheduleError, match="overlap itself"):
        StepSchedule("d", (PhaseDecl("a", overlaps=("a",)),))
    with pytest.raises(ScheduleError, match="cannot overlap"):
        # b depends on a THROUGH c, yet claims to overlap it
        StepSchedule("d", (PhaseDecl("a"),
                           PhaseDecl("c", after=("a",)),
                           PhaseDecl("b", after=("c",),
                                     overlaps=("a",))))
    with pytest.raises(ScheduleError, match="kind"):
        PhaseDecl("a", kind="junk")


def test_schedule_declaration_check_against_compiled_graph():
    rep = sa.audit_text(_overlap_module(64, False), world=8)
    honest = StepSchedule("honest", (
        PhaseDecl("id_all_to_all", kind="collective"),))
    rep.check_against_schedule(honest)
    assert rep.ok
    lying = StepSchedule("lying", (
        PhaseDecl("id_all_to_all", kind="collective",
                  overlaps=("dense",)),
        PhaseDecl("dense", kind="compute")))
    rep.check_against_schedule(lying)
    assert any("does not exist in what XLA emitted" in v
               for v in rep.violations)
    # a declared collective phase the program no longer has
    rep2 = sa.audit_text(_overlap_module(64, False), world=8)
    rep2.check_against_schedule(StepSchedule("gone", (
        PhaseDecl("vanished_exchange", kind="collective"),)))
    assert any("matches no compiled collective" in v
               for v in rep2.violations)


# --------------------------------------------- the real compiled step


def _real_step_report(case="dense"):
    from tools._profcommon import build_case

    import jax
    from jax.sharding import Mesh

    de, cats, batch_tree, dense_params, loss_fn = build_case(
        case, 8, 256)
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    rep = sa.audit_train_step(
        de, loss_fn, optax.sgd(0.5), SparseAdagrad(), cats, batch_tree,
        mesh=mesh, lr_schedule=0.3, dense_params=dense_params,
        with_metrics=False, nan_guard=True, label=f"test/{case}8")
    return de, rep


@pytest.fixture(scope="module")
def real_step_report():
    """The unpipelined step with every slot on the scatter's stream: the
    baseline the contracts were written against."""
    from distributed_embeddings_tpu.parallel import optimizers as opt

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(opt, "sums_densely", lambda *a, **k: False)
        return _real_step_report()


def test_real_step_baseline_serialized_a2a_chain(real_step_report):
    """The acceptance baseline: the unpipelined step's id / out / grad
    all-to-alls are serialized ON the critical path, and the report is
    contract-clean against the layer's own (serialized) schedule."""
    de, rep = real_step_report
    assert rep.ok, rep.violations
    a2a = {c.phase_leaf: c for c in rep.collectives
           if c.op == "all-to-all"}
    assert set(a2a) == {"id_all_to_all", "out_all_to_all",
                        "grad_all_to_all"}
    for c in a2a.values():
        assert c.classification == "serialized", c
        assert c.on_critical_path, c
        assert c.payload_bytes > 0
    assert rep.serialized_collective_fraction > 0.9
    # the schedule phases the orchestrator declares all compiled in
    assert de.schedule.phase(PHASE_ID_EXCHANGE).kind == "collective"
    path_phases = " ".join(p for p, _ in rep.critical_path_phases)
    assert "id_all_to_all" in path_phases
    assert "lookup_" in path_phases
    assert PHASE_OUT_EXCHANGE in path_phases
    assert "grad_all_to_all" in path_phases


@pytest.mark.parametrize("case,scope", [("dense", "small_sum"),
                                        ("ragged", "ragged_sum")])
def test_small_table_sums_read_ids_beside_the_exchanges(case, scope):
    """At this batch (the ragged case: at its capacity) every table of the
    case is small, so the backward sums each slot's cotangents into a dense
    block. Which block rows an id touched is read off the received ids
    alone: a chain independent of the activation and cotangent exchanges,
    which on these toy payloads outweighs them (``tools/schedule_audit.py``
    declares it for both cases). The id exchange, which everything follows,
    stays serialized, but beside the forward's fetch of the ragged case's
    small-table block rows, which reads the slab and no id
    (``parallel/lookup.py:block_bag_sums``): that is all it overlaps."""
    de, rep = _real_step_report(case)
    plan, = de._plan_cache.values()
    assert plan.dense_slots
    a2a = {c.phase_leaf: c for c in rep.collectives if c.op == "all-to-all"}
    if case == "dense":
        assert a2a["id_all_to_all"].classification == "serialized"
    else:
        beside = {p for p, ns in
                  a2a["id_all_to_all"].independent_by_phase.items()
                  if ns > 1}
        assert beside and all("/segment_prefix" in p for p in beside), beside
    for leaf in ("out_all_to_all", "grad_all_to_all"):
        by = a2a[leaf].independent_by_phase
        assert any(p.endswith(scope) and ns > 0
                   for p, ns in by.items()), by


def test_real_step_fake_overlap_schedule_fails(real_step_report):
    """The seeded drill of the acceptance criteria: a StepSchedule
    CLAIMING the id exchange overlaps dense compute, checked against
    the real serialized program, must produce violations."""
    de, rep = real_step_report
    fake = StepSchedule("fake-pipelined", (
        PhaseDecl(PHASE_ID_EXCHANGE, kind="collective",
                  overlaps=(PHASE_DENSE,)),
        PhaseDecl(PHASE_LOOKUP, kind="compute",
                  after=(PHASE_ID_EXCHANGE,)),
        PhaseDecl(PHASE_DENSE, kind="compute")))
    import dataclasses as dc
    fresh = dc.replace(rep, violations=[])
    fresh.check_against_schedule(fake)
    assert any("SERIALIZES collective" in v for v in fresh.violations)
    with pytest.raises(sa.ScheduleGraphError, match="schedule audit"):
        fresh.raise_on_violations()


def test_real_step_graph_is_acyclic_with_roots(real_step_report):
    de, rep = real_step_report
    assert rep.nodes > 50 and rep.edges > rep.nodes // 2
    # report built => topo_order succeeded (cycle-free) and roots exist
    assert rep.critical_path_ns > 0


def test_overlap_claim_verified_against_declared_partner():
    """A claim must be certified against the DECLARED partner's
    independent compute, not any independent chain: the module has a big
    independent `dense_forward_backward` phase, so claiming overlap with
    it passes — but claiming overlap with `lookup_*` (which has no
    independent compute here) must fail even though the collective's
    GLOBAL classification is overlappable."""
    rep = sa.audit_text(_overlap_module(64, True), world=8)
    (c,) = rep.collectives
    assert c.classification == "overlappable"
    assert c.independent_matching(("dense_forward_backward",)) > 0
    assert c.independent_matching(("lookup_*",)) == 0.0
    honest = StepSchedule("honest-claim", (
        PhaseDecl("id_all_to_all", kind="collective",
                  overlaps=("dense_forward_backward",)),
        PhaseDecl("dense_forward_backward", kind="compute")))
    rep.check_against_schedule(honest)
    assert rep.ok, rep.violations
    lying = StepSchedule("wrong-partner", (
        PhaseDecl("id_all_to_all", kind="collective",
                  overlaps=("lookup_*",)),
        PhaseDecl("lookup_*", kind="compute")))
    rep2 = sa.audit_text(_overlap_module(64, True), world=8)
    rep2.check_against_schedule(lying)
    assert any("SERIALIZES collective" in v for v in rep2.violations)
