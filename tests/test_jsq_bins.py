"""The quantized-JSQ bin of adaptive routing (`ref.jsq_bin`): a saturated
path scores the top bin in float32, as the float64 NumPy engine scores
it, and float32 AR/WAR runs whose queues saturate track that engine."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.experiments import execute_points
from repro.kernels import jsq_route, ref
from repro.netsim.fabric import FluidFabric
from repro.scenarios import get_scenario

NBINS, QMAX = 16, 8.0


def _old_bin(q):
    """The bin as the NumPy engine computes it."""
    return np.floor(np.clip(q / QMAX, 0, 1 - 1e-9) * NBINS) + 1.0


def _grid():
    """q / qmax over and past [0, 1]: an even grid, every bin edge and
    its neighbours, and the values next below 1."""
    edges = np.arange(NBINS + 1) / NBINS
    return np.unique(np.concatenate([
        np.linspace(-0.25, 1.25, 200_001), edges,
        np.nextafter(edges, -np.inf), np.nextafter(edges, np.inf),
        [1 - 1e-9, 1 - 5e-10, 2.0, 1e6]]))


@pytest.mark.parametrize("pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("load", [1.0, 2.0])
def test_saturated_path_scores_the_top_bin_in_float32(pallas, load):
    """Spine 0 empty (bin 1), spine 1 at `load` × qmax.  With unit
    weights the fractions are a softmax of minus the bins, so the ratio
    of the two gives spine 1's bin."""
    q = jnp.tile(jnp.array([[0.0, load * QMAX]], jnp.float32), (8, 1))
    ones = jnp.ones_like(q)
    assert np.all(np.asarray(ref.jsq_bin(q[:, 1], nbins=NBINS,
                                         qmax=QMAX)) == NBINS)
    frac = np.asarray(jsq_route.pair_fractions(
        q, ones, ones, nbins=NBINS, temperature=1.0, qmax=QMAX,
        use_pallas=pallas, interpret=True), np.float64)
    assert np.all(frac > 0)
    np.testing.assert_allclose(1.0 - np.log(frac[:, 1] / frac[:, 0]),
                               NBINS, atol=1e-3)


def test_float64_bin_equals_the_numpy_engine_bit_for_bit():
    x = _grid()
    with jax.enable_x64(True):
        got = np.asarray(ref.jsq_bin(jnp.asarray(x * QMAX), nbins=NBINS,
                                     qmax=QMAX))
        assert got.dtype == np.float64
        np.testing.assert_array_equal(got, _old_bin(x * QMAX))
        # whole spine splits: each row puts one grid value beside an
        # empty and a half-full spine.  Bit for bit the parent's jnp
        # split (the old bin, then the same softmax); within an ulp of
        # `FluidFabric`'s NumPy split, whose exp and divide round apart
        n = len(x)
        q = jnp.asarray(np.stack(
            [x * QMAX, np.zeros(n), np.full(n, QMAX / 2)], -1))
        w = jnp.asarray(np.stack(
            [np.ones(n), np.full(n, 0.5), np.ones(n)], -1))
        cap = jnp.ones_like(q)
        split = np.asarray(ref.pair_score_softmax_ref(
            q, cap, w, nbins=NBINS, temperature=1.0, qmax=QMAX))
        logit = -jnp.asarray(_old_bin(np.asarray(q))) / w
        logit -= logit.max(-1, keepdims=True)
        e = jnp.exp(logit)
        sums = e.sum(-1, keepdims=True)
        np.testing.assert_array_equal(split, np.asarray(e / sums))
        want = FluidFabric._jsq_softmax(
            SimpleNamespace(jsq_bins=NBINS, ar_temp=1.0), np.asarray(q),
            np.asarray(cap), np.asarray(w))
        np.testing.assert_allclose(split, want, rtol=1e-15, atol=0)


# per-flow mean goodput, line-rate units: a float32 run of these
# scenarios lands within 1e-7 of the float64 engine (1.7e-8 and 7.3e-8);
# scoring a saturated path one bin too high moved flows by 4.0e-4
# (flap_during_incast) and 5.4e-2 (bisection_fat_tree)
GOODPUT_TOL = 1e-6


def _goodput(spec, compiled, result):
    return {"goodput": np.array(result.mean_goodput, np.float64)}


@pytest.mark.parametrize("routing", ["ar", "war"])
@pytest.mark.parametrize("scenario", ["flap_during_incast",
                                      "bisection_fat_tree"])
def test_float32_run_with_saturated_queues_tracks_the_numpy_engine(
        scenario, routing):
    """Registry scenarios whose pair queues pass qmax (every sink-leaf
    path of the flapping incast; the fat tree's bisection), run by the
    JAX engine in float32 against the NumPy engine in float64."""
    spec = get_scenario(scenario).with_sim(routing=routing)
    want, = execute_points([spec.with_sim(backend="numpy")],
                           backend="numpy", derive=_goodput)
    with jax.enable_x64(False):
        got, = execute_points([spec.with_sim(backend="jax")],
                              backend="jax", derive=_goodput)
    diff = np.abs(got.extra["goodput"] - want.extra["goodput"])
    assert diff.max() < GOODPUT_TOL
