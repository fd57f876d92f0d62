"""Channel-model registry: stationary distributions, temporal correlation,
and the (key, state) -> (gains, state) contract (repro/core/channel.py)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CHANNEL_IDS, CHANNEL_MODELS, ChannelConfig,
                        channel_state_zero, draw_gains, homogeneous_sigmas,
                        make_channel, mobility_rho, resolve_sigmas)
from repro.core.channel import _outage_gain_floor

N = 64
CH = ChannelConfig(n_clients=N)
SIG = homogeneous_sigmas(N)  # sigma=1 -> gains ~ Exp(mean 2), clip inactive
S = int(os.environ.get("REPRO_STATS_SAMPLES", "400"))
Z = 4.5  # CI width in sigmas (deterministic under fixed seeds)


def _rollout(model, key, rounds):
    """Scan a channel model, returning (rounds, N) gains."""

    def body(state, k):
        gains, state = model.step(k, state)
        return state, gains

    state = model.init(jax.random.fold_in(key, 0))
    _, gains = jax.lax.scan(body, state, jax.random.split(key, rounds))
    return np.asarray(gains)


def test_registry_names_and_ids():
    assert set(CHANNEL_MODELS) == {"rayleigh", "rician", "lognormal",
                                   "gauss_markov", "mobility",
                                   "outage_burst"}
    assert CHANNEL_IDS["rayleigh"] == 0
    # ids are append-only: the pre-scenario registry keeps its numbering
    assert CHANNEL_IDS["gauss_markov"] == 3
    with pytest.raises(ValueError):
        make_channel("awgn", SIG, CH)


def test_state_contract():
    """Every model: init -> (2, N) f32 state, step preserves the shape."""
    for name in CHANNEL_MODELS:
        model = make_channel(name, SIG, CH)
        st = model.init(jax.random.PRNGKey(0))
        assert st.shape == (2, N) and st.dtype == jnp.float32, name
        gains, st2 = model.step(jax.random.PRNGKey(1), st)
        assert gains.shape == (N,) and st2.shape == (2, N), name
        # the models clip in float32, so the bounds are the f32-rounded ones
        lo, hi = (np.float32(b) for b in CH.gain_bounds())
        assert gains.min() >= lo and gains.max() <= hi, name


def test_rayleigh_step_is_draw_gains_bitwise():
    """The registry's rayleigh is the paper's draw_gains, bit for bit (the
    pre-registry engines depend on this)."""
    model = make_channel("rayleigh", SIG, CH)
    key = jax.random.PRNGKey(3)
    gains, st = model.step(key, channel_state_zero(N))
    np.testing.assert_array_equal(np.asarray(gains),
                                  np.asarray(draw_gains(key, SIG, CH)))
    np.testing.assert_array_equal(np.asarray(st), 0.0)


def test_rician_k_to_zero_recovers_rayleigh():
    """K -> 0: same stationary gain distribution as Rayleigh (mean 2 sigma^2,
    exponential shape). Compared via moments over many rounds."""
    key = jax.random.PRNGKey(4)
    ric = _rollout(make_channel("rician", SIG, CH, k_factor=1e-6), key, 400)
    ray = _rollout(make_channel("rayleigh", SIG, CH), key, 400)
    # Exponential(2): mean 2, std 2. 400*64 samples -> ~1% standard error.
    assert abs(ric.mean() - ray.mean()) < 0.1
    assert abs(ric.std() - ray.std()) < 0.15
    assert abs(ric.mean() - 2.0) < 0.1


def test_rician_large_k_concentrates():
    """Strong LOS: mean power stays 2 sigma^2 but the spread collapses
    (relative variance (1 + 2K)/(1 + K)^2 -> 0)."""
    key = jax.random.PRNGKey(5)
    ric = _rollout(make_channel("rician", SIG, CH, k_factor=50.0), key, 200)
    ray = _rollout(make_channel("rayleigh", SIG, CH), key, 200)
    assert abs(ric.mean() - 2.0) < 0.1
    assert ric.std() < 0.3 * ray.std()


def test_lognormal_preserves_mean_widens_spread():
    key = jax.random.PRNGKey(6)
    logn = _rollout(make_channel("lognormal", SIG, CH, shadow_db=6.0), key,
                    400)
    ray = _rollout(make_channel("rayleigh", SIG, CH), key, 400)
    assert abs(logn.mean() - ray.mean()) < 0.2     # mean-normalized shadowing
    assert logn.std() > 1.2 * ray.std()            # heavier tails


@pytest.mark.parametrize("rho", [0.0, 0.9])
def test_gauss_markov_autocorrelation(rho):
    """Power autocorrelation of the complex AR(1) field: corr(|g_t|^2,
    |g_{t+1}|^2) = rho^2 (≈ 0 when rho = 0, i.e. i.i.d. Rayleigh)."""
    key = jax.random.PRNGKey(7)
    g = _rollout(make_channel("gauss_markov", SIG, CH, rho=rho), key, 3000)
    x, y = g[:-1].ravel(), g[1:].ravel()
    corr = np.corrcoef(x, y)[0, 1]
    assert abs(corr - rho ** 2) < 0.05, (corr, rho)
    # stationary gain distribution is still Exponential(2 sigma^2)
    assert abs(g.mean() - 2.0) < 0.1


def test_gauss_markov_stationary_init():
    """The t=0 state is drawn from the stationary law — no power ramp-up
    over the first rounds."""
    key = jax.random.PRNGKey(8)
    g = _rollout(make_channel("gauss_markov", SIG, CH, rho=0.95), key, 40)
    # a zero-init field would start at (1 - rho^2) * 2 sigma^2 ≈ 0.2 and ramp
    # up; the stationary init starts at full power (2 sigma^2 ± sample noise)
    assert 1.0 < g[0].mean() < 3.5
    assert 1.2 < g[:5].mean() < 3.0


def test_mobility_delegates_to_gauss_markov_bitwise():
    """``mobility`` is gauss_markov at the Jakes-derived rho — the physical
    parameterization must not change a single bit of the AR(1) math."""
    key = jax.random.PRNGKey(9)
    kw = dict(speed_mps=3.0, carrier_hz=5.9e9, round_s=0.02)
    mob = _rollout(make_channel("mobility", SIG, CH, **kw), key, 50)
    gm = _rollout(make_channel("gauss_markov", SIG, CH,
                               rho=mobility_rho(**kw)), key, 50)
    np.testing.assert_array_equal(mob, gm)


def test_mobility_rho_physics():
    """rho falls with speed/carrier/round length, and the pedestrian
    default sits in the slow-fading regime (strongly correlated)."""
    assert 0.0 < mobility_rho(120.0 / 3.6) < mobility_rho(1.5) < 1.0
    assert mobility_rho(0.0) == 1.0
    assert mobility_rho(1.5, carrier_hz=28e9) < mobility_rho(1.5)
    assert mobility_rho(1.5) > 0.7


def test_outage_burst_validation():
    """Rates are validated when the state is built: an outage probability
    unreachable at the requested burst length must fail loudly."""
    key = jax.random.PRNGKey(10)
    for bad in (dict(outage_p=-0.1), dict(outage_p=1.0),
                dict(burst_len=0.5),
                dict(outage_p=0.9, burst_len=2.0)):  # needs p_enter > 1
        with pytest.raises(ValueError):
            make_channel("outage_burst", SIG, CH, **bad).init(key)


def test_outage_burst_floor_within_bounds():
    """In-outage gains sit AT the dedicated floor — the f32 value rounded
    UP from the f64 clip bound, so a single outage step still satisfies the
    one-step gain contract (every model's fast-path clip saturates one ulp
    lower, at f32(lo), which is where the trajectory min can land)."""
    lo, _ = CH.gain_bounds()
    g = _rollout(make_channel("outage_burst", SIG, CH, outage_p=0.5,
                              burst_len=3.0), jax.random.PRNGKey(11), 200)
    floor = _outage_gain_floor(CH)
    assert floor >= lo
    assert float(g.min()) >= float(np.float32(lo))
    assert (g == np.float32(floor)).mean() > 0.2  # outages actually happen


@pytest.mark.stats
def test_outage_burst_marginal_matches_configured_probability():
    """Stationary outage fraction == outage_p, within a CI derived from the
    sample budget. The Gilbert-Elliott chain is sticky, so the indicator
    variance inflates by (1 + r) / (1 - r) with r = 1 - p_enter - p_recover
    (AR(1) autocorrelation of the state chain); the CI uses the inflated
    sigma so the assertion stays deterministic at any budget."""
    outage_p, burst_len = 0.2, 4.0
    rounds = 4 * S
    g = _rollout(make_channel("outage_burst", SIG, CH, outage_p=outage_p,
                              burst_len=burst_len),
                 jax.random.PRNGKey(12), rounds)
    frac = float((g == np.float32(_outage_gain_floor(CH))).mean())
    p_recover = 1.0 / burst_len
    p_enter = outage_p * p_recover / (1.0 - outage_p)
    r = 1.0 - p_enter - p_recover
    var = outage_p * (1.0 - outage_p) * (1.0 + r) / (1.0 - r)
    sigma = np.sqrt(var / (rounds * N))
    assert abs(frac - outage_p) < Z * sigma, (frac, outage_p, Z * sigma)


@pytest.mark.stats
def test_mobility_autocorrelation_matches_jakes_rho():
    """Power autocorrelation of the mobility channel is rho^2 at the
    Jakes-derived rho (mirror of the gauss_markov autocorrelation test)."""
    kw = dict(speed_mps=10.0, carrier_hz=2.4e9, round_s=0.01)
    rho = mobility_rho(**kw)
    g = _rollout(make_channel("mobility", SIG, CH, **kw),
                 jax.random.PRNGKey(13), 8 * S)
    x, y = g[:-1].ravel(), g[1:].ravel()
    corr = np.corrcoef(x, y)[0, 1]
    assert abs(corr - rho ** 2) < 0.05, (corr, rho)
    assert abs(g.mean() - 2.0) < 0.1  # stationary law still Exp(2 sigma^2)


def test_resolve_sigmas():
    assert resolve_sigmas("homogeneous", 10).shape == (10,)
    het = resolve_sigmas("heterogeneous", 40)
    assert het.shape == (40,) and float(het.min()) < float(het.max())
    explicit = resolve_sigmas(np.full(8, 0.5, np.float32), 8)
    np.testing.assert_allclose(np.asarray(explicit), 0.5)
    with pytest.raises(ValueError):
        resolve_sigmas("bimodal", 10)
    with pytest.raises(ValueError):
        resolve_sigmas(np.ones(4), 8)
