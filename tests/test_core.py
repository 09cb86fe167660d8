"""DR-FL core: layerwise masks, aggregation (incl. property tests), energy."""
import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core import (aggregation, energy, layerwise)
from repro.core.energy import DeviceProfile, DeviceState


# ---------------------------------------------------------------------------
# layerwise
# ---------------------------------------------------------------------------


def test_exit_points_and_masks():
    cfg = get_config("yi-34b")
    assert layerwise.exit_points(cfg) == (15, 30, 45, 60)
    m0 = layerwise.layer_mask(cfg, 0)
    m3 = layerwise.layer_mask(cfg, 3)
    assert float(m0.sum()) == 15 and float(m3.sum()) == 60
    # monotone prefix
    assert bool(jnp.all(m0 <= m3))
    assert layerwise.submodel_fraction(cfg, 0) == pytest.approx(0.25)


def test_stacked_update_mask_shapes():
    cfg = get_smoke_config("yi-34b")
    from repro.models import build
    params = build(cfg).init(jax.random.PRNGKey(0))
    masks = layerwise.stacked_update_mask(cfg, 0, params)
    # stacked block leaves get [L,1,...] masks; embed gets scalar 1
    blk_mask = jax.tree.leaves(masks["blocks"])[0]
    assert blk_mask.shape[0] == cfg.num_layers
    assert float(masks["embed"]["emb"]) == 1.0


# ---------------------------------------------------------------------------
# aggregation properties
# ---------------------------------------------------------------------------


def _tree(key, scale=1.0):
    k1, k2 = jax.random.split(key)
    return {"a": scale * jax.random.normal(k1, (4, 3)),
            "b": scale * jax.random.normal(k2, (2,))}


def test_fedavg_identity_and_mean():
    key = jax.random.PRNGKey(0)
    t = _tree(key)
    out = aggregation.fedavg([t, t, t])
    np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(t["a"]), rtol=1e-6)
    t2 = jax.tree.map(lambda x: -x, t)
    out = aggregation.fedavg([t, t2])
    np.testing.assert_allclose(np.asarray(out["a"]), 0.0, atol=1e-6)


@hypothesis.given(
    w1=st.floats(1.0, 100.0), w2=st.floats(1.0, 100.0),
    m_a=st.integers(0, 1), m_b=st.integers(0, 1))
@hypothesis.settings(max_examples=20, deadline=None)
def test_layerwise_aggregate_properties(w1, w2, m_a, m_b):
    """(1) untouched layers stay exactly; (2) single-client layers copy that
    client; (3) overlap = weighted mean."""
    gp = {"x": jnp.zeros((2, 3))}
    u1 = {"x": jnp.ones((2, 3))}
    u2 = {"x": 3.0 * jnp.ones((2, 3))}
    mask1 = {"x": jnp.asarray([[1.0], [m_a]])}   # layer 0 trained, layer 1 maybe
    mask2 = {"x": jnp.asarray([[1.0], [m_b]])}
    out = aggregation.layerwise_aggregate(gp, [u1, u2], [mask1, mask2],
                                          weights=[w1, w2])
    # layer 0: both trained
    exp0 = (w1 * 1.0 + w2 * 3.0) / (w1 + w2)
    np.testing.assert_allclose(np.asarray(out["x"][0]), exp0, rtol=1e-5)
    den = w1 * m_a + w2 * m_b
    exp1 = 0.0 if den == 0 else (w1 * m_a * 1.0 + w2 * m_b * 3.0) / den
    np.testing.assert_allclose(np.asarray(out["x"][1]), exp1, rtol=1e-5)


def test_fl_allreduce_matches_host_aggregation():
    """Masked psum over a 'pod' axis == layerwise_aggregate (1-device mesh,
    pod size 1 degenerates to identity; also check 1-pod math directly)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    u = {"x": jnp.ones((2, 3))}
    m = {"x": jnp.ones((2, 1))}

    def f(u, m):
        return aggregation.fl_allreduce(u, m, 2.0, "pod")

    out = shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=P())(u, m)
    np.testing.assert_allclose(np.asarray(out["x"]), 1.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# energy model (Eq. 3-7) properties
# ---------------------------------------------------------------------------


@hypothesis.given(
    data=st.integers(50, 2000),
    frac=st.sampled_from([0.25, 0.5, 0.75, 1.0]),
    mbytes=st.floats(1e4, 1e7))
@hypothesis.settings(max_examples=30, deadline=None)
def test_round_cost_eq57(data, frac, mbytes):
    prof = DeviceProfile.from_tier("medium")
    dev = DeviceState(profile=prof, remaining=prof.battery, data_size=data)
    t_tra, t_com, e_tra, e_com = energy.round_cost(dev, mbytes, frac,
                                                   local_epochs=5)
    assert t_tra > 0 and t_com > 0
    # Eq. 7: E = P * T
    assert e_tra == pytest.approx(dev.train_power() * t_tra, rel=1e-6)
    assert e_com == pytest.approx(prof.p_com * t_com, rel=1e-6)
    # Eq. 5: T_com linear in model size; T_tra linear in data
    t_tra2, t_com2, _, _ = energy.round_cost(dev, 2 * mbytes, frac, local_epochs=5)
    assert t_com2 == pytest.approx(2 * t_com, rel=1e-6)
    # a smaller submodel is cheaper to train
    t_small, _, _, _ = energy.round_cost(dev, mbytes, frac / 2, local_epochs=5)
    assert t_small < t_tra


def test_charge_battery_exhaustion():
    prof = DeviceProfile.from_tier("small")
    dev = DeviceState(profile=prof, remaining=10.0, data_size=100)
    ok = energy.charge(dev, 6.0, 3.0)
    assert ok and dev.remaining == pytest.approx(1.0)
    ok = energy.charge(dev, 6.0, 3.0)   # not enough: dies, energy wasted
    assert not ok and dev.remaining == 0.0 and not dev.alive
    assert not energy.charge(dev, 0.1, 0.1)   # dead stays dead


def test_fleet_heterogeneous():
    fleet = energy.make_fleet(30, seed=1)
    tiers = {d.profile.tier for d in fleet}
    assert len(tiers) >= 2
    assert all(d.remaining == d.profile.battery for d in fleet)
    assert energy.total_remaining(fleet) == pytest.approx(
        sum(d.profile.battery for d in fleet))
