"""DABench core: Eq. 1-5 unit tests, property tests on metric invariants,
HLO-analyzer verification against hand-built modules, section partitioner
invariants."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ARCHS, SHAPES, MeshConfig
from repro.core import metrics, sections
from repro.core.hlo_analysis import analyze_hlo, parse_module
from repro.core.roofline import roofline

settings.register_profile("metrics", max_examples=50, deadline=None)
settings.load_profile("metrics")


# ------------------------------------------------------------- equations
def test_eq1_allocation():
    assert metrics.allocation_ratio(92, 100) == pytest.approx(0.92)
    assert metrics.allocation_ratio(0, 0) == 0.0


def test_eq2_weighted_allocation():
    # two sections: 3s at 50%, 1s at 100% -> (3*0.5 + 1*1)/4
    secs = [(3.0, 50, 100), (1.0, 100, 100)]
    assert metrics.weighted_allocation(secs) == pytest.approx(0.625)


def test_eq3_load_imbalance_exact():
    # equal resources, throughputs (1, 2): LI = (1/2)(1 + 0.5) = 0.75
    assert metrics.load_imbalance([1, 1], [1, 2]) == pytest.approx(0.75)
    assert metrics.load_imbalance([1, 1, 1], [5, 5, 5]) == pytest.approx(1.0)


def test_eq4_weighted_li():
    assert metrics.weighted_load_imbalance(
        [(1.0, 1.0), (3.0, 0.5)]) == pytest.approx((1 + 1.5) / 4)


def test_eq5_arithmetic_intensity():
    # paper form: 6PBS / (4P + act)
    ai = metrics.arithmetic_intensity(1e8, 8, 1024, 0.0)
    assert ai == pytest.approx(6 * 1e8 * 8 * 1024 / (4e8))


@given(st.lists(st.tuples(st.floats(0.1, 100), st.floats(0.1, 100)),
                min_size=1, max_size=20))
def test_li_invariants(pairs):
    """Property: LI in (0, 1]; ==1 iff all throughputs equal."""
    r = [p[0] for p in pairs]
    t = [p[1] for p in pairs]
    li = metrics.load_imbalance(r, t)
    assert 0.0 < li <= 1.0 + 1e-9
    if len(set(round(x, 9) for x in t)) == 1:
        assert li == pytest.approx(1.0)


@given(st.lists(st.floats(0.01, 10), min_size=2, max_size=16))
def test_li_scale_invariance(ts):
    """Scaling all throughputs by a constant leaves LI unchanged."""
    r = [1.0] * len(ts)
    li1 = metrics.load_imbalance(r, ts)
    li2 = metrics.load_imbalance(r, [t * 7.3 for t in ts])
    assert li1 == pytest.approx(li2, rel=1e-9)


def test_mxu_tile_efficiency():
    assert metrics.mxu_tile_efficiency(8, 128, 128) == pytest.approx(1.0)
    assert metrics.mxu_tile_efficiency(4, 128, 128) == pytest.approx(0.5)
    assert 0 < metrics.mxu_tile_efficiency(100, 100, 100) < 1


# ----------------------------------------------------------- HLO analyzer
HLO_SAMPLE = """
HloModule test, num_partitions=4

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %c1 = s32[] constant(1)
  %i2 = s32[] add(%i, %c1)
  %w = f32[16,16]{1,0} constant({...})
  %y = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag = f32[8,32]{1,0} all-gather(%y), channel_id=1, replica_groups=[2,2]<=[4], dimensions={1}
  %z = f32[8,16]{1,0} slice(%ag), slice={[0:8],[0:16]}
  ROOT %t = (s32[], f32[8,16]{1,0}) tuple(%i2, %z)
}

%cond (p2: (s32[], f32[8,16])) -> pred[] {
  %p2 = (s32[], f32[8,16]{1,0}) parameter(0)
  %i3 = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i3, %n), direction=LT
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t0 = (s32[], f32[8,16]{1,0}) tuple(%c0, %a)
  %w0 = (s32[], f32[8,16]{1,0}) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[8,16]{1,0} get-tuple-element(%w0), index=1
}
"""


def test_hlo_parse():
    comps, entry = parse_module(HLO_SAMPLE)
    assert entry == "main"
    assert set(comps) == {"body", "cond", "main"}
    assert any(i.opcode == "dot" for i in comps["body"])


def test_hlo_trip_count_expansion():
    r = analyze_hlo(HLO_SAMPLE)
    # dot: 2*8*16*16 flops per iteration, 10 iterations
    assert r.dot_flops == pytest.approx(10 * 2 * 8 * 16 * 16)
    ags = [c for c in r.collectives if c.opcode == "all-gather"]
    assert len(ags) == 1
    assert ags[0].count == pytest.approx(10)
    assert ags[0].bytes == pytest.approx(8 * 16 * 4)   # operand bytes
    assert ags[0].group_size == 2


def test_roofline_terms():
    r = analyze_hlo(HLO_SAMPLE)
    rl = roofline(r, chips=4, model_flops=1e6)
    assert rl.compute_s == pytest.approx(r.flops / 197e12)
    assert rl.dominant in ("compute", "memory", "collective")
    d = rl.to_dict()
    assert set(d) >= {"compute_s", "memory_s", "collective_s", "dominant"}


# ------------------------------------------------------------- sections
@pytest.mark.parametrize("mode", ["O0", "O1", "O3"])
@pytest.mark.parametrize("arch", ["granite-3-8b", "arctic-480b", "rwkv6-3b"])
def test_section_partitioner(mode, arch):
    cfg = ARCHS[arch]
    rep = sections.analyze(cfg, SHAPES["train_4k"], MeshConfig(), mode)
    assert 0 < rep.allocation <= 1.0
    assert 0 < rep.load_imbalance <= 1.0
    assert rep.total_runtime > 0
    if mode == "O0":
        assert rep.n_sections > cfg.num_layers  # finer than per-layer


def test_sections_flops_conserved():
    """Partitioning must not change total flops (O0 == O1 == O3 totals)."""
    cfg = ARCHS["granite-3-8b"]
    ops = sections.build_op_graph(cfg, SHAPES["train_4k"], MeshConfig())
    total = sum(o.flops for o in ops)
    for mode in ("O0", "O1", "O3"):
        secs = sections.partition(ops, mode)
        assert sum(s.flops for s in secs) == pytest.approx(total)


def test_section_graph_tracks_model_flops():
    """Structural op-graph flops within 2x of the 6ND analytic estimate."""
    cfg = ARCHS["granite-3-8b"]
    shape = SHAPES["train_4k"]
    ops = sections.build_op_graph(cfg, shape, MeshConfig())
    total = sum(o.flops for o in ops)
    model = 6.0 * cfg.param_count() * shape.global_batch * shape.seq_len
    assert 0.5 < total / model < 2.0
