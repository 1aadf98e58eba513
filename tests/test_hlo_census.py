"""Optimized-HLO pass census (analysis/hlo_census.py) + the SGD dedup cut.

The acceptance contract of ISSUE 7: the compiled hybrid step's ops
attribute exactly to their ``obs.scope`` phases (dense / ragged /
row-sliced / MpInputs configs); the ``dedup`` phase compiles to ZERO row
ops under SparseSGD and to the pinned sort+segment-sum budget under the
stateful family on the dedup-regime shapes; seeded violations (an extra
gather pass, a float convert round-trip) are flagged by the declarative
PassBudget contracts; and an N-step SparseSGD trajectory is BITWISE
identical with and without the dedup pass (``DETPU_SGD_DEDUP=1``) on the
8-virtual-device mesh. Census runs compile abstractly (lower+compile,
nothing executes) under JAX_PLATFORMS=cpu (conftest); only the bitwise
equivalence test dispatches real steps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from distributed_embeddings_tpu.analysis import (
    CensusError, PassBudget, census_of_text, census_step_fn,
    census_train_step, default_contracts)
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, SparseAdagrad, SparseSGD, init_hybrid_state,
    make_hybrid_train_step)
from tools._profcommon import build_case

WORLD = 8
B = 16


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    assert len(devs) >= WORLD, "conftest should force 8 CPU devices"
    return Mesh(np.array(devs[:WORLD]), ("data",))


def _census(config, opt, world, mesh=None, **kw):
    de, cats, batch_tree, dense_params, loss_fn = build_case(
        config, world, B)
    return census_train_step(
        de, loss_fn, optax.sgd(0.5), opt, cats, batch_tree, mesh=mesh,
        lr_schedule=0.3, dense_params=dense_params, **kw)


# --------------------------------------------------------------- the parser


HANDWRITTEN = """\
HloModule jit_step

%fused_computation.1 (p0: f32[64,8], p1: s32[16]) -> f32[16,8] {
  %p0 = f32[64,8]{1,0} parameter(0)
  %p1 = s32[16]{0} parameter(1)
  ROOT %gather.1 = f32[16,8]{1,0} gather(f32[64,8]{1,0} %p0, s32[16]{0} %p1), metadata={op_name="jit(step)/detpu/lookup_w8_d/detpu/packed_gather/gather"}
}

ENTRY %main (a: f32[64,8], ids: s32[16]) -> f32[64,8] {
  %a = f32[64,8]{1,0} parameter(0)
  %ids = s32[16]{0} parameter(1)
  %fusion.1 = f32[16,8]{1,0} fusion(f32[64,8]{1,0} %a, s32[16]{0} %ids), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/detpu/lookup_w8_d/detpu/packed_gather/gather"}
  %sort.1 = s32[16]{0} sort(s32[16]{0} %ids), dimensions={0}, metadata={op_name="jit(step)/detpu/sparse_apply_w8/detpu/dedup/sort"}
  %convert.1 = bf16[16,8]{1,0} convert(f32[16,8]{1,0} %fusion.1), metadata={op_name="jit(step)/detpu/sparse_apply_w8/convert_element_type"}
  %convert.2 = f32[16,8]{1,0} convert(bf16[16,8]{1,0} %convert.1), metadata={op_name="jit(step)/detpu/sparse_apply_w8/convert_element_type"}
  %all-to-all.1 = (f32[2,8]{1,0}, f32[2,8]{1,0}) all-to-all(f32[2,8]{1,0} %fusion.1, f32[2,8]{1,0} %fusion.1), metadata={op_name="jit(step)/detpu/out_all_to_all/all_to_all"}
  ROOT %while.1 = f32[64,8]{1,0} while(f32[64,8]{1,0} %a), condition=%cond, body=%body, metadata={op_name="jit(step)/detpu/sparse_apply_w8/scatter-add"}
}
"""


def test_parser_on_handwritten_hlo():
    """Pure text -> report: opcode normalization (while->scatter on the
    CPU lowering), tuple shapes, scope-path attribution, convert pairs,
    and the float round-trip metric — no compilation involved."""
    rep = census_of_text(HANDWRITTEN, label="hand", world=2)
    # the gather appears twice: once as the fused computation's body
    # instruction, once is the fusion wrapper (counted as fusion, not
    # gather)
    assert rep.passes("*packed_gather", "gather") == 1
    assert rep.phases["lookup_w8_d/packed_gather"].fusions == 1
    assert rep.passes("dedup", "sort") == 1
    assert rep.passes("sparse_apply_w8", "scatter") == 1  # the while
    assert rep.passes("out_all_to_all", "all_to_all") == 1
    sa = rep.phases["sparse_apply_w8"]
    assert sa.convert_pairs == {"f32->bf16": 1, "bf16->f32": 1}
    assert sa.roundtrips() == 1
    assert rep.passes("sparse_apply_*", "convert_roundtrip") == 1
    # contracts: the seeded round-trip and a dedup budget both fire
    rep.check([PassBudget("sparse_apply_*", "convert_roundtrip", 0),
               PassBudget("dedup", "sort", 0, reason="sgd")])
    assert len(rep.violations) == 2
    with pytest.raises(CensusError, match="pass budget"):
        rep.raise_on_violations()
    # renderings stay consistent with the dataclass
    md = rep.markdown()
    assert "| phase |" in md and "`dedup`" not in md  # leaf rides its path
    assert "lookup_w8_d/packed_gather" in md
    js = rep.to_json()
    assert js["ok"] is False
    assert js["phases"]["sparse_apply_w8/dedup"]["sort"] == 1


def test_min_passes_underrun_flagged():
    rep = census_of_text(HANDWRITTEN)
    rep.check([PassBudget("dedup", "gather", min_passes=1, max_passes=9)])
    assert any("underrun" in v for v in rep.violations)


def test_per_path_min_fires_when_phase_is_gone():
    # a renamed/dropped scope must trip a per_path min contract, not
    # vacuously match nothing and report ok
    rep = census_of_text(HANDWRITTEN)
    rep.check([PassBudget("no_such_phase", "sort", min_passes=1,
                          max_passes=9, per_path=True)])
    assert any("underrun" in v for v in rep.violations)


TPU_LAYOUT = """\
HloModule jit_step

ENTRY %main (a: f32[64,8], ids: s32[16]) -> f32[16,8] {
  %a = f32[64,8]{1,0:T(8,128)} parameter(0)
  %ids = s32[16]{0:T(256)} parameter(1)
  ROOT %gather.1 = f32[16,8]{1,0:T(8,128)S(1)} gather(f32[64,8]{1,0:T(8,128)} %a, s32[16]{0:T(256)} %ids), metadata={op_name="jit(step)/detpu/lookup_w8_d/detpu/packed_gather/gather"}
}
"""


def test_parser_on_tpu_layout_shapes():
    """Post-layout-assignment TPU HLO carries tiling/memory-space inside
    the layout braces (``{1,0:T(8,128)S(1)}``) — the parser must not
    silently skip those instruction lines (an unmatched line means the
    pass-budget gate passes vacuously on the real backend)."""
    rep = census_of_text(TPU_LAYOUT)
    assert rep.total_instructions == 3
    assert rep.passes("*packed_gather", "gather") == 1


def test_unparseable_module_fails_loudly():
    """census_step_fn must never return an empty census: zero parsed
    instructions means THIS backend's HLO text defeated the parser and
    every downstream budget would hold vacuously."""

    class _Fake:
        def lower(self, *a):
            return self

        def compile(self):
            return self

        def as_text(self):
            return "not hlo at all\n"

    with pytest.raises(CensusError, match="parsed 0 instructions"):
        census_step_fn(_Fake(), ())


def test_min_only_contract_is_floor_not_cap():
    # max_passes defaults to unbounded, so a floor-only contract guards a
    # pass's existence without also capping it
    rep = census_of_text(HANDWRITTEN)
    rep.check([PassBudget("dedup", "sort", min_passes=1)])
    assert not rep.violations


def test_min_greater_than_max_rejected():
    with pytest.raises(ValueError, match="can never hold"):
        PassBudget("dedup", "sort", max_passes=0, min_passes=1)


# ------------------------------------------------- phase attribution (mesh)


@pytest.mark.parametrize("config", ["dense", "ragged", "row_sliced"])
def test_phase_attribution_8dev(config, mesh):
    """Every reference config compiles with its ops attributed to the
    expected scope paths: 3 all-to-all passes in their exchange phases,
    gathers confined to the lookup groups (<= 2 per group: the packed
    gather + its lane extract), forward and apply phases present."""
    rep = _census(config, SparseAdagrad(), WORLD, mesh=mesh)
    assert rep.ok, rep.violations
    assert rep.passes("id_all_to_all", "all_to_all") == 1
    assert rep.passes("out_all_to_all", "all_to_all") == 1
    assert rep.passes("grad_all_to_all", "all_to_all") == 1
    assert rep.passes("*", "all_to_all") == 3
    assert rep.passes("*lookup_*", "gather") >= 1
    assert any(p.startswith("embedding_forward") for p in rep.phases)
    assert any("sparse_apply" in p for p in rep.phases)
    rep.check([PassBudget("*lookup_*", "gather", max_passes=2,
                          per_path=True)])
    assert rep.ok, rep.violations


def test_mp_inputs_phase_attribution(mesh):
    """dp_input=False (MpInputs) skips the id exchange: the census shows
    0 id-exchange all-to-all passes and keeps the out/grad pair."""
    configs = [{"input_dim": 20 + 6 * i, "output_dim": 4,
                "combiner": ["sum", None, "mean"][i % 3]}
               for i in range(10)]
    de = DistributedEmbedding(configs, world_size=WORLD, dp_input=False)
    rng = np.random.default_rng(0)
    inputs = []
    for cfg in configs:
        hot = 1 if cfg["combiner"] is None else 3
        shape = (B,) if hot == 1 else (B, hot)
        inputs.append(rng.integers(0, cfg["input_dim"], size=shape
                                   ).astype(np.int32))
    mp = de.pack_mp_inputs(inputs)

    def loss_fn(dp, emb_outs, batch):
        n, y = batch
        x = jnp.concatenate([e.reshape(e.shape[0], -1) for e in emb_outs],
                            axis=1)
        return jnp.mean((x @ dp["w"] + n @ dp["v"] - y) ** 2)

    cols = sum(int(c["output_dim"]) for c in configs)
    dense_params = {"w": jax.ShapeDtypeStruct((cols, 1), jnp.float32),
                    "v": jax.ShapeDtypeStruct((3, 1), jnp.float32)}
    batch_tree = (jax.ShapeDtypeStruct((B, 3), jnp.float32),
                  jax.ShapeDtypeStruct((B, 1), jnp.float32))
    rep = census_train_step(de, loss_fn, optax.sgd(0.5), SparseAdagrad(),
                            mp, batch_tree, mesh=mesh,
                            dense_params=dense_params)
    assert rep.ok, rep.violations
    assert rep.passes("id_all_to_all", "all_to_all") == 0
    assert rep.passes("*", "all_to_all") == 2


# ----------------------------------------------------- the dedup pass budget


def test_sgd_dedup_budget_zero_8dev(mesh):
    """The pass cut, statically verified: on the dedup-regime shapes the
    SparseSGD build compiles a completely empty dedup phase (the default
    contracts enforce it; needs_dedup=False)."""
    rep = _census("bigvocab", SparseSGD(), WORLD, mesh=mesh)
    assert rep.ok, rep.violations
    for kind in ("sort", "scatter", "cumsum", "gather"):
        assert rep.passes("dedup", kind) == 0, kind
    assert not SparseSGD.needs_dedup


def test_adagrad_dedup_budget_unchanged_8dev(mesh):
    """The stateful family keeps its dedup pass on the same shapes —
    pinned exactly (1 sort + 2 segment-sum scatters per width group; one
    w8 group here), so a refactor that silently loses or duplicates the
    pass must update this number deliberately."""
    rep = _census("bigvocab", SparseAdagrad(), WORLD, mesh=mesh)
    assert rep.ok, rep.violations
    assert SparseAdagrad.needs_dedup
    assert rep.passes("dedup", "sort") == 1
    assert rep.passes("dedup", "scatter") == 2
    rep.check([PassBudget("dedup", "sort", max_passes=8, min_passes=1)])
    assert rep.ok, rep.violations


# ------------------------------------------------------- seeded violations


def test_seeded_extra_gather_pass_flagged():
    """A smuggled extra gather inside a lookup-group scope exceeds the
    <=2-per-group budget and fails --strict (the ISSUE drill)."""

    def step(slab, ids):
        with jax.named_scope("detpu/lookup_w8_d"):
            with jax.named_scope("detpu/packed_gather"):
                a = jnp.take(slab, ids, axis=0, mode="clip")
                b = jnp.take(slab, ids + 1, axis=0, mode="clip")
                c = jnp.take(slab, ids + 2, axis=0, mode="clip")
        return a.sum() + b.sum() + c.sum()

    rep = census_step_fn(
        jax.jit(step),
        (jax.ShapeDtypeStruct((100, 8), jnp.float32),
         jax.ShapeDtypeStruct((16,), jnp.int32)),
        label="seeded_gather",
        contracts=[PassBudget("*lookup_*", "gather", max_passes=2,
                              per_path=True)])
    assert not rep.ok
    assert any("gather" in v and "budget" in v for v in rep.violations), \
        rep.violations


def test_seeded_convert_roundtrip_flagged():
    """A float32 value squeezed through bf16 and back inside the apply
    phase is a silent-precision-loss hazard the census flags."""

    def step(x):
        with jax.named_scope("detpu/sparse_apply_w8"):
            y = x.astype(jnp.bfloat16).astype(jnp.float32)
            return (y * 2.0).sum()

    rep = census_step_fn(
        jax.jit(step), (jax.ShapeDtypeStruct((64, 8), jnp.float32),),
        label="seeded_roundtrip",
        contracts=[PassBudget("sparse_apply_*", "convert_roundtrip", 0)])
    assert rep.passes("sparse_apply_*", "convert_roundtrip") >= 1
    assert not rep.ok


# ---------------------------------------- the dedup-skip bitwise equivalence


def _grid(a, q=6):
    """Quantize onto the 2**-q grid so every update addition in the test
    is exact (no rounding anywhere => float addition re-associates freely
    => with/without dedup MUST be bitwise identical, not just close)."""
    return jnp.round(a * (1 << q)) / (1 << q)


def _bitwise_case(mesh, key=0):
    configs = [{"input_dim": 32, "output_dim": 8, "combiner": None}
               for _ in range(8)]
    de = DistributedEmbedding(configs, world_size=WORLD)

    def loss_fn(dp, emb_outs, batch):
        del batch
        x = jnp.concatenate([e.reshape(e.shape[0], -1) for e in emb_outs],
                            axis=1)
        # linear loss => cotangents are dp["w"] entries (grid values)
        return jnp.sum(x @ dp["w"]) * (2.0 ** -6)

    # dense side frozen (lr 0): w must stay on its coarse grid, or the
    # emb cotangents (= w * 2**-6) would gain mantissa bits every step
    # and the slab additions would start rounding — the exactness the
    # bitwise assertion rests on
    tx = optax.sgd(0.0)
    # duplicate-heavy ids: 16 draws from 8 distinct rows per table/step
    rng = np.random.default_rng(7)
    steps = [
        ([jnp.asarray(rng.integers(0, 8, size=(B,)), jnp.int32)
          for _ in configs],
         (jnp.zeros((B, 1), jnp.float32),))
        for _ in range(8)]
    w_np = rng.normal(size=(64, 1)).astype(np.float32)

    def fresh_state():
        # fresh arrays every run: the step donates its whole state, so a
        # buffer shared between the A and B runs would be deleted by A
        dense_params = {"w": _grid(jnp.asarray(w_np), q=3)}
        st = init_hybrid_state(de, SparseSGD(), dense_params, tx,
                               jax.random.key(key), mesh=mesh)
        return st._replace(emb_params=jax.tree.map(_grid, st.emb_params))

    return de, loss_fn, tx, steps, fresh_state


def test_sgd_trajectory_bitwise_equal_with_and_without_dedup(
        mesh, monkeypatch):
    """ISSUE 7 acceptance: 8 SparseSGD steps on the 8-device mesh, run
    with the dedup pass compiled OUT (default) and compiled IN
    (DETPU_SGD_DEDUP=1), end in bitwise-identical states. The data is
    engineered onto a power-of-two grid so every addition is exact —
    equality then proves the two programs apply the same updates to the
    same rows (any dropped/duplicated/misrouted id would break it), with
    no float-reassociation noise to hide behind."""
    de, loss_fn, tx, steps, fresh_state = _bitwise_case(mesh)

    def run():
        step = make_hybrid_train_step(de, loss_fn, tx, SparseSGD(),
                                      mesh=mesh, lr_schedule=0.5)
        state = fresh_state()
        for cats, batch in steps:
            _, state = step(state, cats, batch)
        return state

    monkeypatch.delenv("DETPU_SGD_DEDUP", raising=False)
    plain = run()
    monkeypatch.setenv("DETPU_SGD_DEDUP", "1")
    forced = run()

    for pa, pb in ((plain.emb_params, forced.emb_params),
                   (plain.dense_params, forced.dense_params)):
        la = jax.tree_util.tree_leaves_with_path(pa)
        lb = jax.tree_util.tree_leaves(pb)
        assert len(la) == len(lb)
        for (path, a), b in zip(la, lb):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"leaf {jax.tree_util.keystr(path)} diverged")


def test_sgd_dedup_escape_hatch_changes_the_program(mesh, monkeypatch):
    """The A/B knob must actually flip the compiled program: under
    DETPU_SGD_DEDUP=1 the SparseSGD step's dedup phase is non-empty
    (sort present), while the default build keeps it at zero (tested
    above). Static census only — nothing executes."""
    de, loss_fn, tx, _, fresh_state = _bitwise_case(mesh)
    state = jax.eval_shape(fresh_state)
    cats = [jax.ShapeDtypeStruct((B,), jnp.int32) for _ in range(8)]
    batch = (jax.ShapeDtypeStruct((B, 1), jnp.float32),)
    monkeypatch.setenv("DETPU_SGD_DEDUP", "1")
    rep = census_train_step(
        de, loss_fn, tx, SparseSGD(), cats, batch, mesh=mesh,
        lr_schedule=0.5, state=state, contracts=[],
        label="sgd_dedup_forced")
    assert rep.passes("dedup", "sort") >= 1
    # and default_contracts must NOT demand an empty dedup phase while
    # the hatch is set (the A/B build is a legitimate program)
    assert not any(c.phase == "dedup" for c in default_contracts(SparseSGD()))
    monkeypatch.delenv("DETPU_SGD_DEDUP")
    assert any(c.phase == "dedup" for c in default_contracts(SparseSGD()))
