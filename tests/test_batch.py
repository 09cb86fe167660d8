"""Bucketed-vmap client executor (repro.fl.batch) + stacked Pallas
aggregation (repro.fl.server.aggregate_drfl_stacked) vs the per-client /
list-based references.

Parity contracts:
* the executor's padded schedules replay data.loader.epoch_batches exactly
  (same host RNG, same sample order, wrap-around padding included);
* bucketed-vmap deltas match the per-client reference — vmap/scan fusion
  reorders float reductions, so single-step runs agree to ~1e-5 and
  multi-step runs to ~2e-3 (ULP differences amplified through SGD), never
  bit-exact by construction;
* stacked aggregation matches list-based ``aggregate_drfl``: ~1e-6 fresh
  (kernel reduction order differs at the ULP level), allclose under
  staleness decay, and s=0 is BIT-EXACT vs fresh (same compiled branch);
* a sync round at n=256 issues <= 4 client-update program executions (one
  per populated submodel bucket) and <= 4 program compilations.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.runtime import compile_guard
from repro.data.loader import epoch_batches
from repro.fl import FLConfig, resolve_client_executor, run_simulation
from repro.fl import batch as fl_batch
from repro.fl import client as fl_client
from repro.fl import server as fl_server
from repro.models import cnn
from repro.models.family import get_family


def _data(n=300, hw=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, hw, hw, 3)).astype(np.float32)
    y = rng.integers(0, 10, n)
    return x, y


def _params(width=0.06):
    return cnn.init(jax.random.PRNGKey(0), 10, width_mult=width)


# ---------------------------------------------------------------------------
# schedule parity with the per-client loader
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_i,batch,epochs", [(70, 32, 2), (20, 32, 3),
                                              (64, 16, 1), (5, 8, 2)])
def test_schedule_matches_epoch_batches(n_i, batch, epochs):
    part = np.arange(100, 100 + n_i)
    x = np.arange(1000)
    seed = fl_client.client_update_seed(0, 3, 7)
    sched = fl_batch.client_schedule(part, seed, epochs, batch)
    ref = []
    rng = np.random.default_rng(seed)
    for _ in range(epochs):
        for xb, _ in epoch_batches(x[part], x[part], batch, rng):
            ref.append(xb)
    assert len(sched) == len(ref)
    for row, xb in zip(sched, ref):
        np.testing.assert_array_equal(x[row], xb)


# ---------------------------------------------------------------------------
# bucketed-vmap deltas vs the per-client reference
# ---------------------------------------------------------------------------


def _cohort_parity(method, epochs, atol):
    x, y = _data()
    params = _params()
    # mixed sizes (incl. tiny wrap-around client) and mixed model indices
    parts = [np.arange(0, 40), np.arange(40, 52), np.arange(52, 120),
             np.arange(120, 140)]
    ids = [0, 1, 2, 3]
    ms = [0, 1, 1, 3]
    seeds = [fl_client.client_update_seed(0, 0, i) for i in ids]
    res = fl_batch.run_cohort(method, params, x, y, parts, ids, ms, seeds,
                              epochs=epochs, batch=32, lr=0.05)
    fn = getattr(fl_client, f"{method}_client_update")
    for dev, m, delta, w, loss in res.unstacked():
        d_ref, l_ref = fn(params, m, x[parts[dev]], y[parts[dev]],
                          epochs=epochs, batch=32, lr=0.05, seed=seeds[dev])
        assert w == float(len(parts[dev]))
        if method == "drfl":
            # reference deltas are full-structure with exact zeros outside
            # the submodel; the executor returns the submodel prefix
            assert all(bool(jnp.all(l == 0)) for l in jax.tree.leaves(
                {"stages": d_ref["stages"][m + 1:],
                 "exits": d_ref["exits"][m + 1:]}))
            d_ref = {"stem": d_ref["stem"], "stages": d_ref["stages"][:m + 1],
                     "exits": d_ref["exits"][:m + 1]}
        for a, b in zip(jax.tree.leaves(delta), jax.tree.leaves(d_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=atol, rtol=0)
        assert loss == pytest.approx(l_ref, abs=1e-3)


def test_bucketed_deltas_match_per_client_single_epoch():
    # one epoch, tiny shards -> few steps: reductions barely reorder
    _cohort_parity("drfl", epochs=1, atol=2e-4)


def test_bucketed_deltas_match_per_client_multi_epoch():
    # the executor's patches-conv (batched-GEMM) formulation reorders conv
    # reductions (~1e-6/step vs lax.conv); SGD amplifies that chaotically
    # over multi-epoch runs — documented tolerance on ~1e-2-scale deltas
    _cohort_parity("drfl", epochs=2, atol=6e-3)


@pytest.mark.parametrize("method", ["heterofl", "scalefl"])
def test_bucketed_deltas_match_baselines(method):
    _cohort_parity(method, epochs=1, atol=5e-4)


def test_bucket_padding_is_inert():
    """Pad rows (pow2 participant padding) carry weight 0.0 and the real
    rows are unchanged by their presence."""
    x, y = _data()
    params = _params()
    parts = [np.arange(0, 30), np.arange(30, 60), np.arange(60, 90)]
    ids, ms = [0, 1, 2], [2, 2, 2]
    seeds = [fl_client.client_update_seed(0, 0, i) for i in ids]
    res = fl_batch.run_cohort("drfl", params, x, y, parts, ids, ms, seeds,
                              epochs=1, batch=32, lr=0.05)
    (b,) = res.buckets
    leaves = jax.tree.leaves(b.stacked_delta)
    assert all(l.shape[0] == 4 for l in leaves)          # pow2(3) = 4
    assert b.weights == [30.0, 30.0, 30.0, 0.0]
    assert len(b.participants) == 3


# ---------------------------------------------------------------------------
# stacked aggregation vs the list-based reference
# ---------------------------------------------------------------------------


def _deltas(params, n, seed=1):
    key = jax.random.PRNGKey(seed)
    deltas = [jax.tree.map(
        lambda a, j=j: jax.random.normal(jax.random.fold_in(key, j),
                                         a.shape) * 0.01, params)
        for j in range(n)]
    idxs = [j % 4 for j in range(n)]
    weights = [float(5 + j) for j in range(n)]
    return deltas, idxs, weights


def test_stacked_aggregate_matches_list_reference():
    params = _params()
    deltas, idxs, w = _deltas(params, 7)
    ref = fl_server.aggregate_drfl(params, deltas, idxs, w, server_lr=0.7)
    got = fl_server.aggregate_drfl_from_list(params, deltas, idxs, w,
                                             server_lr=0.7)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   rtol=0)


def test_stacked_aggregate_staleness_matches_list_reference():
    params = _params()
    deltas, idxs, w = _deltas(params, 7)
    stal = [0, 2, 0, 5, 1, 0, 3]
    ref = fl_server.aggregate_drfl(params, deltas, idxs, w, server_lr=0.7,
                                   staleness=stal, staleness_decay=0.5)
    got = fl_server.aggregate_drfl_from_list(params, deltas, idxs, w,
                                             server_lr=0.7, staleness=stal,
                                             staleness_decay=0.5)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   rtol=0)


def test_stacked_aggregate_zero_staleness_bitexact_vs_fresh():
    params = _params()
    deltas, idxs, w = _deltas(params, 5)
    fresh = fl_server.aggregate_drfl_from_list(params, deltas, idxs, w)
    s0 = fl_server.aggregate_drfl_from_list(params, deltas, idxs, w,
                                            staleness=[0] * 5)
    for a, b in zip(jax.tree.leaves(fresh), jax.tree.leaves(s0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stacked_aggregate_untrained_layers_unchanged():
    params = _params()
    deltas, _, _ = _deltas(params, 1)
    out = fl_server.aggregate_drfl_from_list(params, deltas, [0], [1.0])
    for a, b in zip(jax.tree.leaves(params["stages"][3]),
                    jax.tree.leaves(out["stages"][3])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(params["stem"]),
                    jax.tree.leaves(out["stem"])):
        assert not np.allclose(np.asarray(a), np.asarray(b))


def test_stacked_aggregate_pallas_kernel_interpret():
    """The Pallas layer_agg kernel (interpret mode) plugs into the same
    stacked path and agrees with the einsum fallback and the list path."""
    params = cnn.init(jax.random.PRNGKey(0), 10, width_mult=0.02)
    deltas, idxs, w = _deltas(params, 5)
    ref = fl_server.aggregate_drfl(params, deltas, idxs, w, server_lr=0.7)
    got = fl_server.aggregate_drfl_from_list(
        params, deltas, idxs, w, server_lr=0.7, use_kernel=True,
        interpret=True)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-5)


def test_stacked_aggregate_staleness_with_kernel_interpret():
    params = cnn.init(jax.random.PRNGKey(0), 10, width_mult=0.02)
    deltas, idxs, w = _deltas(params, 4)
    stal = [1, 0, 4, 2]
    ref = fl_server.aggregate_drfl(params, deltas, idxs, w, staleness=stal)
    got = fl_server.aggregate_drfl_from_list(
        params, deltas, idxs, w, staleness=stal, use_kernel=True,
        interpret=True)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-5)


# ---------------------------------------------------------------------------
# engine wiring: batched executor through sync + async
# ---------------------------------------------------------------------------


def _small_cfg(**kw):
    base = dict(n_devices=6, n_rounds=3, participation=0.5, n_train=500,
                local_epochs=1, method="drfl", selector="greedy", seed=1)
    base.update(kw)
    return FLConfig(**base)


def test_sync_engine_batched_executor_parity():
    """Selection, energy and scheduling are executor-independent (exact);
    accuracies agree to vmap-reduction tolerance."""
    h_pc = run_simulation(_small_cfg(client_executor="perclient"))
    h_b = run_simulation(_small_cfg(client_executor="batched"))
    assert h_b["participants"] == h_pc["participants"]
    assert h_b["model_choices"] == h_pc["model_choices"]
    assert h_b["energy"] == h_pc["energy"]
    assert h_b["round_time"] == h_pc["round_time"]
    np.testing.assert_allclose(h_b["acc_mean"], h_pc["acc_mean"], atol=0.06)


@pytest.mark.parametrize("method", ["heterofl", "scalefl"])
def test_sync_engine_batched_baselines(method):
    h_pc = run_simulation(_small_cfg(method=method,
                                     client_executor="perclient"))
    h_b = run_simulation(_small_cfg(method=method,
                                    client_executor="batched"))
    assert h_b["participants"] == h_pc["participants"]
    np.testing.assert_allclose(h_b["acc_mean"], h_pc["acc_mean"], atol=0.06)


def test_async_engine_batched_executor():
    """Micro-bucketed dispatch-tick training: deltas precomputed at send
    time, consumed at completion events, staleness decay still applied."""
    cfg = _small_cfg(n_devices=8, n_rounds=4, engine_mode="async",
                     client_executor="batched")
    h = run_simulation(cfg)
    h_pc = run_simulation(dataclasses.replace(cfg,
                                              client_executor="perclient"))
    assert h["n_tasks"] == h_pc["n_tasks"]
    assert h["n_aggregations"] == len(h["staleness"])
    assert np.isfinite(h["acc_mean"]).all()
    np.testing.assert_allclose(h["acc_mean"], h_pc["acc_mean"], atol=0.06)


def test_resolve_client_executor_auto_rules():
    assert resolve_client_executor(_small_cfg()) == "perclient"
    big_small_model = _small_cfg(n_devices=128, hw=8, width_mult=0.06,
                                 batch_size=8)
    big_paper_model = _small_cfg(n_devices=128, hw=16, width_mult=0.25,
                                 batch_size=32)
    if jax.default_backend() == "cpu":
        assert resolve_client_executor(big_small_model) == "batched"
        # paper-width steps are BLAS-bound on CPU: batching cannot win
        assert resolve_client_executor(big_paper_model) == "perclient"
    assert resolve_client_executor(
        _small_cfg(client_executor="batched")) == "batched"
    with pytest.raises(ValueError):
        resolve_client_executor(_small_cfg(client_executor="nope"))


# ---------------------------------------------------------------------------
# dispatch-count regression guard (ISSUE 3 acceptance)
# ---------------------------------------------------------------------------


def test_dispatch_count_sync_round_n256():
    """A sync round at n=256 issues <= 4 client-update program executions
    (one per populated submodel bucket) and <= 4 compilations."""
    cfg = FLConfig(n_devices=256, n_rounds=1, participation=0.1,
                   n_train=1536, local_epochs=1, method="drfl",
                   selector="greedy", seed=0, energy_scale=0.05,
                   hw=8, width_mult=0.06, batch_size=8,
                   client_executor="batched")
    fl_batch.reset_counters()
    h = run_simulation(cfg)
    assert len(h["acc_mean"]) == 1
    assert 0 < fl_batch.COUNTERS["executions"] <= 4
    assert fl_batch.COUNTERS["compiles"] <= 4
    # and the auto rule picks the batched path for this CPU-budget config
    if jax.default_backend() == "cpu":
        assert resolve_client_executor(
            dataclasses.replace(cfg, client_executor="auto")) == "batched"


def test_repeat_cohort_reuses_compiled_programs():
    x, y = _data()
    params = _params()
    parts = [np.arange(0, 40), np.arange(40, 80)]
    ids, ms = [0, 1], [1, 3]
    seeds = [fl_client.client_update_seed(0, 0, i) for i in ids]
    kw = dict(epochs=1, batch=32, lr=0.05)
    fl_batch.reset_counters()
    fl_batch.run_cohort("drfl", params, x, y, parts, ids, ms, seeds, **kw)
    first = fl_batch.COUNTERS["compiles"]
    # the reusable runtime guard consumes the same COUNTERS dict: a repeat
    # cohort of identical shapes may not compile anything new
    with compile_guard(counters=fl_batch.COUNTERS, max_new=0):
        fl_batch.run_cohort("drfl", params, x, y, parts, ids, ms, seeds,
                            **kw)
    assert fl_batch.COUNTERS["executions"] == 2 * first


def test_counters_count_step_slots_and_real_steps():
    """``slots`` counts every P_pad x T_pad step a bucket program ran and
    ``real_steps`` the unmasked ones: the padding share is their ratio."""
    x, y = _data()
    fam = get_family("mlp")
    params = fam.init(jax.random.PRNGKey(0), 10, width_mult=0.06, hw=8)
    parts = [np.arange(0, 40), np.arange(40, 120), np.arange(120, 136)]
    ids, ms = [0, 1, 2], [1, 1, 3]
    seeds = [fl_client.client_update_seed(0, 0, i) for i in ids]
    kw = dict(epochs=1, batch=32)
    buckets = fl_batch.bucket_cohort(ids, ms, parts, seeds,
                                     [float(len(p)) for p in parts], **kw)
    fl_batch.reset_counters()
    fl_batch.run_cohort("drfl", params, x, y, parts, ids, ms, seeds,
                        lr=0.05, family=fam, **kw)
    # model 1: clients of 1 and 2 steps in a [2, 2] schedule; model 3: one
    # client of 16 images, one wrap-around batch
    assert fl_batch.COUNTERS["slots"] == sum(
        b.valid.size for b in buckets) == 4 + 1
    assert fl_batch.COUNTERS["real_steps"] == sum(
        int(b.valid.sum()) for b in buckets) == 3 + 1
