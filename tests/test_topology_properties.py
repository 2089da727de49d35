"""Hypothesis property suite for topology invariants (ISSUE 5
satellite) over BOTH fabric kinds:

  * max-flow symmetry — every fault primitive degrades up/down link
    pairs together, so `maxflow_matrix` stays symmetric under any fault
    schedule;
  * monotone non-increase — no fault may increase any pair's max-flow;
  * capacity-proportional bisection after `random_fail` — the mean
    surviving cross-cut max-flow over a batch of draws brackets between
    the per-path survival law of the fabric's hop count ((1-f)^2 for
    the 2-stage leaf-spine, (1-f)^4 for 4-hop cross-pod fat-tree paths)
    and the raw capacity fraction (1-f): the quantitative form of §6.4's claim that the
    multiplane degrades capacity-proportionally while the hierarchy
    strands surviving capacity;
  * the fat-tree fault-timeline compiler matches the callback-driven
    event closures slot by slot (the leaf-spine twin lives in
    `test_scenario_properties.py`).
"""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed; property tests skipped")

from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.netsim.jx.events import compile_fault_timeline  # noqa: E402
from repro.netsim.topology import (FatTree, LeafSpine,  # noqa: E402
                                   maxflow_matrix)
from repro.scenarios import (FaultSpec, ScenarioSpec, SimSpec,  # noqa: E402
                             TopologySpec, WorkloadSpec)
from repro.scenarios.compile import build_topology, make_events  # noqa: E402

SETTINGS = dict(max_examples=30, deadline=None)


# ---------------------------------------------------------------------------
# hypothesis: fault invariants on both kinds
# ---------------------------------------------------------------------------

def _ls_topos():
    return st.builds(
        lambda L, S, P: LeafSpine(n_leaves=L, n_spines=S,
                                  hosts_per_leaf=2, n_planes=P),
        st.integers(2, 4), st.integers(2, 6), st.integers(1, 3))


def _ft_topos():
    return st.builds(
        lambda pods, lpp, A, cpa, P: FatTree(
            n_pods=pods, leaves_per_pod=lpp, n_aggs=A, n_cores=A * cpa,
            hosts_per_leaf=2, n_planes=P),
        st.integers(2, 3), st.integers(1, 3), st.integers(1, 4),
        st.integers(1, 3), st.integers(1, 2))


def _apply_random_fault(t, rng) -> None:
    kind = rng.integers(5 if t.kind == "fat_tree" else 3)
    p = int(rng.integers(t.n_planes))
    if kind == 0:
        t.fail_uplink(p, int(rng.integers(t.n_leaves)),
                      int(rng.integers(t.up.shape[2])),
                      float(rng.choice([0.5, 1.0])))
    elif kind == 1:
        t.trim_leaf_uplinks(p, int(rng.integers(t.n_leaves)),
                            float(rng.choice([0.25, 0.75])))
    elif kind == 2:
        t.random_link_failures(rng, float(rng.choice([0.1, 0.3])))
    elif kind == 3:
        t.fail_core_link(p, int(rng.integers(t.n_pods)),
                         int(rng.integers(t.n_cores)),
                         float(rng.choice([0.5, 1.0])))
    else:
        t.fail_agg(p, int(rng.integers(t.n_pods)),
                   int(rng.integers(t.n_aggs)))


@given(data=st.one_of(_ls_topos(), _ft_topos()),
       seed=st.integers(0, 2 ** 16), n_faults=st.integers(0, 4))
@settings(**SETTINGS)
def test_maxflow_symmetric_and_monotone_under_faults(data, seed, n_faults):
    t = data
    rng = np.random.default_rng(seed)
    prev = maxflow_matrix(t)
    assert np.allclose(prev, prev.T)
    for _ in range(n_faults):
        _apply_random_fault(t, rng)
        mf = maxflow_matrix(t)
        assert np.allclose(mf, mf.T), "symmetric capacities -> symmetric"
        assert (mf <= prev + 1e-9).all(), "faults never increase max-flow"
        assert (mf >= -1e-12).all()
        prev = mf


# The cut ratio of one draw scatters around its mean: over 4,000 draws
# its sd is 0.026-0.040 on the leaf-spine below and 0.059-0.106 on the
# fat-tree (f = 0.05-0.2), and the leaf-spine's mean IS the lower law
# (1-f)^2, so a per-draw bracket fails about one draw in 135.  The law
# is held by the mean of DRAWS draws instead, within Z standard errors
# (the batch's sample sd / sqrt(DRAWS)): a sound fabric misses by more
# than 5 standard errors with probability under 3e-7 a check.
DRAWS = 200
Z = 5.0


@given(kind=st.sampled_from(["leaf_spine", "fat_tree"]),
       seed=st.integers(0, 2 ** 16),
       frac=st.sampled_from([0.05, 0.1, 0.2]))
@settings(**SETTINGS)
def test_capacity_proportional_bisection_after_random_fail(kind, seed,
                                                          frac):
    """Cross-cut max-flow after uniform random link failures brackets,
    in expectation, between the hop-count survival law and raw capacity
    proportionality.  The fat-tree's 4-hop exponent IS the hierarchy
    penalty the multiplane design deletes."""
    if kind == "leaf_spine":
        pristine = LeafSpine(n_leaves=8, n_spines=16, hosts_per_leaf=2,
                             n_planes=2)
        hops = 2
    else:
        pristine = FatTree(n_pods=2, leaves_per_pod=4, n_aggs=8,
                           n_cores=16, hosts_per_leaf=2, core_link_cap=4.0)
        hops = 4
    L = pristine.n_leaves
    cut = np.ix_(np.arange(L // 2), np.arange(L // 2, L))
    base = maxflow_matrix(pristine)[cut].sum()
    rng = np.random.default_rng(seed)
    ratios = []
    for _ in range(DRAWS):
        t = pristine.copy()
        t.random_link_failures(rng, frac)
        ratios.append(maxflow_matrix(t)[cut].sum() / base)
    mean = float(np.mean(ratios))
    tol = Z * float(np.std(ratios, ddof=1)) / np.sqrt(DRAWS)
    assert (1 - frac) ** hops - tol <= mean <= (1 - frac) + tol, \
        (kind, frac, mean, tol)


# ---------------------------------------------------------------------------
# fat-tree fault timeline == callback mutations, slot by slot
# ---------------------------------------------------------------------------

FT_TOPO = st.builds(
    TopologySpec, kind=st.just("fat_tree"),
    n_leaves=st.just(4), n_pods=st.just(2),
    n_aggs=st.sampled_from([1, 2]), n_cores=st.sampled_from([2, 4]),
    hosts_per_leaf=st.integers(2, 3), n_planes=st.integers(1, 2))


def _ft_fault_strategy(topo: TopologySpec, slots: int):
    planes = st.integers(-1, topo.n_planes - 1)
    start = st.integers(0, slots - 1)
    stop = st.one_of(st.none(), st.integers(1, slots + 10))
    frac = st.sampled_from([0.25, 0.5, 1.0])
    leaf = st.integers(0, topo.n_leaves - 1)
    agg = st.integers(0, topo.n_aggs - 1)
    period = st.integers(1, slots)
    return st.one_of(
        st.builds(FaultSpec, kind=st.just("link_kill"), start_slot=start,
                  stop_slot=stop, plane=planes, leaf=leaf, spine=agg,
                  frac=frac),
        st.builds(FaultSpec, kind=st.just("link_flap"), start_slot=start,
                  stop_slot=stop, period=period,
                  duty=st.sampled_from([0.25, 0.5]), plane=planes,
                  leaf=leaf, spine=agg, frac=frac),
        st.builds(FaultSpec, kind=st.just("core_kill"), start_slot=start,
                  stop_slot=stop, plane=planes,
                  pod=st.integers(0, topo.n_pods - 1),
                  core=st.integers(0, topo.n_cores - 1), frac=frac),
        st.builds(FaultSpec, kind=st.just("cascade"), start_slot=start,
                  period=period, plane=planes,
                  pod=st.integers(0, topo.n_pods - 1),
                  spines=st.lists(agg, min_size=1, max_size=2,
                                  unique=True).map(tuple)),
        st.builds(FaultSpec, kind=st.just("leaf_trim"), start_slot=start,
                  plane=planes, leaf=leaf, frac=frac),
        st.builds(FaultSpec, kind=st.just("random_fail"),
                  start_slot=start, frac=st.sampled_from([0.2, 0.5])),
        st.builds(FaultSpec, kind=st.just("random_fail"),
                  start_slot=start, plane=planes, frac=st.just(1.0),
                  count=st.integers(1, 3)),
        st.builds(FaultSpec, kind=st.just("straggler"), start_slot=start,
                  stop_slot=stop, plane=planes,
                  host=st.integers(0, topo.n_hosts - 1), frac=frac),
    )


@st.composite
def _ft_fault_specs(draw):
    topo = draw(FT_TOPO)
    slots = draw(st.integers(4, 30))
    faults = draw(st.lists(_ft_fault_strategy(topo, slots), min_size=0,
                           max_size=3))
    return ScenarioSpec(
        name="prop_ft_faults", topo=topo,
        workloads=(WorkloadSpec("pairs", pairs=((0, topo.n_hosts - 1),)),),
        faults=tuple(faults), sim=SimSpec(slots=slots),
        workload_seed=draw(st.integers(0, 2 ** 16))).validate()


@given(spec=_ft_fault_specs())
@settings(**SETTINGS)
def test_ft_timeline_matches_callback_mutations(spec):
    tl = compile_fault_timeline(spec)
    for arr in (tl.up, tl.down, tl.access, tl.up2, tl.down2):
        assert (arr >= 0).all()
    events, _ = make_events(spec)
    topo = build_topology(spec.topo)
    for t in range(spec.sim.slots):
        events(t, topo)
        np.testing.assert_allclose(
            tl.up[t] * spec.topo.uplink_cap, topo.up, rtol=0, atol=1e-12,
            err_msg=f"stage-A uplinks diverge at slot {t}")
        np.testing.assert_allclose(
            tl.down[t] * spec.topo.uplink_cap, topo.down, rtol=0,
            atol=1e-12, err_msg=f"stage-A downlinks diverge at slot {t}")
        np.testing.assert_allclose(
            tl.up2[t] * spec.topo.core_cap, topo.up2, rtol=0, atol=1e-12,
            err_msg=f"stage-B uplinks diverge at slot {t}")
        np.testing.assert_allclose(
            tl.down2[t] * spec.topo.core_cap, topo.down2, rtol=0,
            atol=1e-12, err_msg=f"stage-B downlinks diverge at slot {t}")
        np.testing.assert_allclose(
            tl.access[t] * spec.topo.access_cap, topo.access, rtol=0,
            atol=1e-12, err_msg=f"access diverges at slot {t}")
