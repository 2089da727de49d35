"""Interpret-mode oracle parity for the netsim hot-path Pallas kernels.

These kernels are TPU-gated in production (`kernels.backend
.pallas_enabled`), so without this suite their Pallas bodies would never
execute in CI.  Every test forces `use_pallas=True, interpret=True` on
CPU and checks the kernel against its `ref.py` oracle — including
non-power-of-two block tails and float64 inputs (the kernels must cast
their operands to float32 themselves; historically `pair_fractions`
passed x64 operands straight into a float32 `pallas_call` and crashed).

The last test drives the whole engine with `REPRO_NETSIM_PALLAS=1`, the
way the CI interpret job runs it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import jsq_route, link_load, plb_select, queue_ecn, ref

RNG = np.random.default_rng


def _f64(rng, *shape, lo=0.0, hi=1.0):
    # float64 host arrays: canonicalized to f32 without x64, genuine
    # f64 operands (the historical crash) when the x64 CI job runs this
    return rng.uniform(lo, hi, shape)


@pytest.mark.parametrize("mode", ["spx", "dcqcn", "agg", "swlb"])
@pytest.mark.parametrize("F,P,bp", [(37, 3, 16), (64, 2, 256),
                                    (129, 4, 64)])
def test_plane_split_interpret(mode, F, P, bp):
    rng = RNG(0)
    rate = _f64(rng, F, P, lo=0.05)
    elig = rng.uniform(size=(F, P)) > 0.25
    elig[:, 0] = True
    demand = _f64(rng, F)
    got = plb_select.plane_split(
        jnp.asarray(rate), jnp.asarray(elig), jnp.asarray(demand),
        mode=mode, min_rate=0.05, bp=bp, use_pallas=True, interpret=True)
    want = ref.plane_split_ref(
        jnp.asarray(rate), jnp.asarray(elig), jnp.asarray(demand),
        mode=mode, min_rate=0.05)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("P,L,S,br", [(3, 5, 7, 16), (1, 8, 8, 128),
                                      (2, 9, 4, 32)])
def test_pair_fractions_interpret(P, L, S, br):
    rng = RNG(1)
    q = _f64(rng, P, L, L, S, hi=8.0)
    cap = _f64(rng, P, L, L, S)
    cap[rng.uniform(size=cap.shape) < 0.15] = 0.0
    cap[..., 0] = np.maximum(cap[..., 0], 0.1)       # one alive spine
    w = cap * _f64(rng, P, L, L, S, lo=0.25)
    got = jsq_route.pair_fractions(
        jnp.asarray(q), jnp.asarray(cap), jnp.asarray(w), nbins=16,
        temperature=1.0, qmax=8.0, br=br, use_pallas=True,
        interpret=True)
    want = ref.pair_score_softmax_ref(
        jnp.asarray(q), jnp.asarray(cap), jnp.asarray(w), nbins=16,
        temperature=1.0, qmax=8.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, atol=1e-5)


@pytest.mark.parametrize("N,bp", [(37, 16), (300, 256)])
def test_plb_select_interpret(N, bp):
    rng = RNG(2)
    P = 4
    ra = jnp.asarray(_f64(rng, P))
    el = jnp.asarray((rng.uniform(size=P) > 0.2).astype(np.float64))
    el = el.at[0].set(1.0)
    lq = jnp.asarray(_f64(rng, P))
    tx = jnp.asarray(_f64(rng, N, hi=0.5))
    h = jnp.asarray(rng.integers(0, 1 << 30, N), jnp.uint32)
    got = plb_select.plb_select(ra, el, lq, tx, h, bp=bp,
                                interpret=True)
    want = ref.plb_select_ref(ra, el, lq, tx, h)
    assert bool((got == want).all())


@pytest.mark.parametrize("N,bp", [(37, 16), (512, 256)])
def test_jsq_route_interpret(N, bp):
    rng = RNG(3)
    ports = 16
    queues = jnp.asarray(_f64(rng, ports))
    up = jnp.asarray((np.arange(ports) % 7 != 0).astype(np.float64))
    w = jnp.asarray(_f64(rng, ports, lo=0.25))
    h = jnp.asarray(rng.integers(0, 1 << 30, N), jnp.uint32)
    got = jsq_route.jsq_route(queues, up, w, h, bp=bp, interpret=True)
    want = ref.jsq_route_ref(queues, up, w, h)
    assert bool((got == want).all())


@pytest.mark.parametrize("P,R,C,br", [(3, 37, 11, 16), (2, 64, 8, 128)])
def test_bucket_load_bottleneck_interpret(P, R, C, br):
    rng = RNG(4)
    g = jnp.asarray(_f64(rng, P, R, C))
    cap = jnp.asarray(_f64(rng, P, R, lo=0.1, hi=2.0))
    got_l, got_f = link_load.bucket_load_bottleneck(
        g, cap, ordered=False, br=br, use_pallas=True, interpret=True)
    want_l, want_f = ref.load_bottleneck_ref(g, cap, eps=link_load.EPS,
                                             ordered=False)
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_f), np.asarray(want_f),
                               rtol=1e-5, atol=1e-6)


def test_bottleneck_interpret_odd_shape():
    rng = RNG(5)
    cap = jnp.asarray(_f64(rng, 2, 5, 7, lo=0.1))
    load = jnp.asarray(_f64(rng, 2, 5, 7, hi=2.0))
    got = link_load.bottleneck(cap, load, br=8, use_pallas=True,
                               interpret=True)
    want = ref.bottleneck_ref(cap, load, eps=link_load.EPS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert got.shape == cap.shape


def test_queue_update_interpret():
    rng = RNG(6)
    q = jnp.asarray(_f64(rng, 2, 8, 8, hi=4.0))
    load = jnp.asarray(_f64(rng, 2, 8, 8, hi=2.0))
    cap = jnp.asarray(_f64(rng, 2, 8, 8))
    cap = cap.at[0, 0, 0].set(0.0)                  # dead link
    got_q, got_u = queue_ecn.queue_update(
        q, load, cap, q_cap=16.0, br=8, use_pallas=True, interpret=True)
    want_q, want_u = ref.queue_update_ref(q, load, cap, q_cap=16.0)
    np.testing.assert_allclose(np.asarray(got_q), np.asarray(want_q),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_u), np.asarray(want_u),
                               rtol=1e-5, atol=1e-5)
    assert float(got_q[0, 0, 0]) == 0.0


_NIC_KW = dict(base_rtt_us=6.0, slot_us=10.0, ecn_thresh=4.0,
               target_rtt_us=12.0, min_rate=0.01, md=0.7, ai=0.08,
               rtt_gain=0.15, dcqcn_ai=0.01, alpha_g=0.0625)


@pytest.mark.parametrize("mode", ["spx", "dcqcn", "agg"])
@pytest.mark.parametrize("F,P,bp", [(37, 3, 16), (300, 2, 128)])
def test_nic_update_interpret(mode, F, P, bp):
    rng = RNG(7)
    qmean = jnp.asarray(_f64(rng, F, P, hi=12.0))
    rate = jnp.asarray(_f64(rng, F, P, lo=0.05))
    alpha = jnp.asarray(_f64(rng, F, P))
    esr = jnp.asarray(rng.uniform(size=(F, 1)) > 0.5)
    got = queue_ecn.nic_update(qmean, rate, alpha, esr, mode=mode,
                               bp=bp, use_pallas=True, interpret=True,
                               **_NIC_KW)
    want = ref.nic_update_ref(qmean, rate, alpha, esr, mode=mode,
                              **_NIC_KW)
    for g, w, name in zip(got, want, ("rtt", "ecn", "rate", "alpha")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


def test_engine_pallas_interpret_smoke(monkeypatch):
    """The whole slot loop through the Pallas (interpret) kernels — the
    configuration the CI `REPRO_NETSIM_PALLAS=1` job runs.  f32 interpret
    kernels track the jnp fallback closely but not bit-exactly, so pin a
    loose envelope on the headline metric."""
    from repro.scenarios.registry import get_scenario
    from repro.scenarios.runner import run_point

    spec = get_scenario("fig9_single_all2all").with_sim(
        slots=60, backend="jax")
    base = run_point(spec).mean_goodput
    monkeypatch.setenv("REPRO_NETSIM_PALLAS", "1")
    got = run_point(spec).mean_goodput
    assert np.isfinite(got) and got > 0
    assert got == pytest.approx(base, rel=0.05)


@pytest.mark.parametrize("shape,br", [((3, 37, 29), 8), ((2, 5, 7), 256),
                                      ((4096,), 8), ((9, 1000), 16)])
def test_lane_kernels_bit_identical(shape, br):
    """`queue_update` and `bottleneck` run on (rows, 128) lane slabs in
    (br, 128) blocks; multi-block grids, zero-padded row tails and
    sub-tile inputs all reproduce the float32 oracles bit for bit."""
    rng = RNG(8)
    q = jnp.asarray(_f64(rng, *shape, hi=4.0), jnp.float32)
    load = jnp.asarray(_f64(rng, *shape, hi=2.0), jnp.float32)
    cap = jnp.asarray(_f64(rng, *shape), jnp.float32)
    cap = cap.reshape(-1).at[::7].set(0.0).reshape(shape)   # dead links
    got_q, got_u = queue_ecn.queue_update(
        q, load, cap, q_cap=16.0, br=br, use_pallas=True, interpret=True)
    want_q, want_u = ref.queue_update_ref(q, load, cap, q_cap=16.0)
    np.testing.assert_array_equal(np.asarray(got_q), np.asarray(want_q))
    np.testing.assert_array_equal(np.asarray(got_u), np.asarray(want_u))
    got_f = link_load.bottleneck(cap, load, br=br, use_pallas=True,
                                 interpret=True)
    want_f = ref.bottleneck_ref(cap, load, eps=link_load.EPS)
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))
    assert got_q.shape == got_f.shape == shape


def test_lane_layout_rejects_unaligned_block_rows():
    with pytest.raises(ValueError, match="multiple of 8"):
        link_load.bottleneck(jnp.ones(4), jnp.ones(4), br=12,
                             use_pallas=True, interpret=True)


@pytest.mark.parametrize("mode", ["spx", "dcqcn", "agg", "swlb"])
def test_plane_split_bit_identical(mode):
    """The float32-mask kernel body reproduces the bool-mask oracle bit
    for bit, including all-ineligible rows and the spx fallback to the
    eligible set when every plane sits below `min_rate`."""
    rng = RNG(9)
    F, P = 300, 4
    rate = rng.uniform(0.0, 1.0, (F, P)).astype(np.float32)
    rate[::5] = 0.01                                # spx fallback rows
    elig = rng.uniform(size=(F, P)) > 0.3
    elig[::11] = False                              # nothing eligible
    demand = rng.uniform(size=F).astype(np.float32)
    args = (jnp.asarray(rate), jnp.asarray(elig), jnp.asarray(demand))
    got = plb_select.plane_split(*args, mode=mode, min_rate=0.05, bp=64,
                                 use_pallas=True, interpret=True)
    want = ref.plane_split_ref(*args, mode=mode, min_rate=0.05)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
