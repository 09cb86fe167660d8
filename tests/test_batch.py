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
import weakref

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


def _as_buckets(params, deltas, idxs, weights):
    """Full-structure client deltas grouped as the executor hands them:
    a bucket per submodel of pow2 rows, pad rows zero with weight 0.0."""
    fam = get_family("cnn")
    out = []
    for m in sorted(set(idxs)):
        js = [j for j, i in enumerate(idxs) if i == m]
        subs = [fam.submodel_tree(deltas[j], m) for j in js]
        pad = (1 << (len(js) - 1).bit_length()) - len(js)
        subs += [jax.tree.map(jnp.zeros_like, subs[0])] * pad
        out.append((m, jax.tree.map(lambda *a: jnp.stack(a), *subs),
                    [weights[j] for j in js] + [0.0] * pad, None))
    return out


def test_stacked_aggregate_program_follows_client_rows_alone():
    """Pad rows stay out of the stack, so the averaging program's shape is
    the round's client rows alone: another split of as many clients
    across submodels compiles no new averaging program."""
    params = _params()
    deltas, _, w = _deltas(params, 4)
    for k, idxs in enumerate(([3, 3, 3, 1], [0, 2, 0, 2])):
        ref = fl_server.aggregate_drfl(params, deltas, idxs, w,
                                       server_lr=0.7)
        buckets = _as_buckets(params, deltas, idxs, w)
        assert sum(len(b[2]) for b in buckets) == (5, 4)[k]
        with compile_guard(fl_server._stacked_agg_program, max_new=1 - k):
            got, valid = fl_server.aggregate_drfl_stacked(
                params, buckets, server_lr=0.7, with_stats=True)
        assert np.asarray(valid).tolist() == [True] * 4
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=0)


@pytest.mark.parametrize("poison", [float("nan"), float("inf"), 1e30])
def test_stacked_aggregate_quarantines_client_rows(poison):
    """A poisoned client row is left out of the mean and flagged in the
    validity, which has one entry per client row in bucket order."""
    params = _params()
    deltas, _, w = _deltas(params, 4)
    idxs = [3, 1, 3, 3]
    deltas[2] = jax.tree.map(lambda a: jnp.full_like(a, poison), deltas[2])
    got, valid = fl_server.aggregate_drfl_stacked(
        params, _as_buckets(params, deltas, idxs, w), with_stats=True)
    # submodel 1's client 1, then submodel 3's clients 0, 2 and 3
    assert np.asarray(valid).tolist() == [True, True, False, True]
    keep = [0, 1, 3]
    ref = fl_server.aggregate_drfl(params, [deltas[j] for j in keep],
                                   [idxs[j] for j in keep],
                                   [w[j] for j in keep])
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        assert np.isfinite(np.asarray(b)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   rtol=0)


def test_stacked_aggregate_stacks_one_client_row_at_a_time():
    """Compiled memory at a small CNN: stacking writes a bucket's client
    rows into the round's stack in place, one client at a time, so its
    temporaries hold no bucket of full-model-width rows however many rows
    the bucket has (the averaging program's share is compiled for a TPU,
    with its kernel, in tests/test_tpu_compile.py)."""
    fam = get_family("cnn")
    params = _params(width=0.25)
    t = fam.stack_template(params)
    row_bytes = t.n_rows * t.seg * 4
    for p in (4, 8):
        delta = jax.tree.map(lambda a: jnp.zeros((p,) + a.shape, a.dtype),
                             fam.submodel_params("drfl", params, 1))
        mem = fl_server._stack_bucket_rows.lower(
            jnp.zeros((p, t.n_rows, t.seg), jnp.float32),
            jnp.zeros((p, t.n_rows), jnp.float32), jnp.ones((p,), bool),
            params, delta, 0, p - 1, family=fam, model_idx=1,
            validate=True,
            mag_cap=fl_server.DELTA_MAG_CAP).compile().memory_analysis()
        # a client's row and its quarantined copy
        assert mem.temp_size_in_bytes < 3 * row_bytes, (p, mem)


# ---------------------------------------------------------------------------
# engine wiring: batched executor through sync + async
# ---------------------------------------------------------------------------


def _small_cfg(**kw):
    base = dict(n_devices=6, n_rounds=3, participation=0.5, n_train=500,
                local_epochs=1, method="drfl", selector="greedy", seed=1)
    base.update(kw)
    return FLConfig(**base)


@pytest.mark.parametrize("method", ["drfl", "scalefl"])
def test_sync_round_drops_its_deltas_before_the_next_cohort(monkeypatch,
                                                            method):
    """A round's stacked deltas are freed once aggregated: none is alive
    when the next round's bucket programs allocate theirs, so the device
    never holds two rounds of deltas."""
    held, alive = [], []
    orig = fl_batch.run_cohort

    def run_cohort(*args, **kwargs):
        alive.append(sum(ref() is not None for ref in held))
        res = orig(*args, **kwargs)
        held.extend(weakref.ref(leaf) for b in res.buckets
                    for leaf in jax.tree.leaves(b.stacked_delta))
        return res

    monkeypatch.setattr(fl_batch, "run_cohort", run_cohort)
    run_simulation(_small_cfg(method=method, client_executor="batched"))
    assert held and alive == [0, 0, 0]


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
    assert fl_batch.COUNTERS["skipped_slots"] == 0


# ---------------------------------------------------------------------------
# clients in turn: each client's own steps, one client after another
# ---------------------------------------------------------------------------


@pytest.fixture
def clients_in_turn(monkeypatch):
    """Sets which path a family's bucket program takes.  The path is read
    while the program traces, so the compiled programs are dropped on both
    sides of the switch."""
    def force(fam, on):
        fl_batch._bucket_program.clear_cache()
        monkeypatch.setattr(fam, "bucket_clients_in_turn", lambda: on)
    yield force
    monkeypatch.undo()
    fl_batch._bucket_program.clear_cache()


def _family_params(name, width=0.06):
    fam = get_family(name)
    return fam, fam.init(jax.random.PRNGKey(0), 10, width_mult=width, hw=8)


#: (epochs, shard sizes): three clients of model 1 in a bucket of four
#: rows, so one pad row; 12 images is a wrap-around client at batch 32
IN_TURN_CASES = {"one_epoch": (1, (40, 12, 68)),
                 "multi_epoch": (3, (70, 12, 100))}


def _one_bucket(sizes, epochs, model=1):
    starts = np.cumsum((0,) + sizes)
    parts = [np.arange(a, b) for a, b in zip(starts[:-1], starts[1:])]
    ids = list(range(len(parts)))
    seeds = [fl_client.client_update_seed(0, 0, i) for i in ids]
    (b,) = fl_batch.bucket_cohort(ids, [model] * len(ids), parts, seeds,
                                  [float(len(p)) for p in parts],
                                  epochs=epochs, batch=32)
    return b


def _program(fam, params, bucket, x, y, program=None):
    program = program or fl_batch._bucket_program
    sub = fam.submodel_params("drfl", params, bucket.model_idx)
    deltas, losses = program(sub, jnp.asarray(x), jnp.asarray(y),
                             jnp.asarray(bucket.gather),
                             jnp.asarray(bucket.valid), method="drfl",
                             lr=0.05, family=fam)
    return jax.tree.map(np.asarray, deltas), np.asarray(losses)


@pytest.mark.parametrize("case", sorted(IN_TURN_CASES))
@pytest.mark.parametrize("family", ["mlp", "cnn"])
def test_clients_in_turn_match_lanes(clients_in_turn, family, case):
    """Same deltas and losses as the vmapped lanes, and pad rows exactly
    0: the in-turn body runs the same steps without the padded ones, on
    the training set as rows, as the engine holds it on that path."""
    epochs, sizes = IN_TURN_CASES[case]
    x, y = _data()
    fam, params = _family_params(family)
    bucket = _one_bucket(sizes, epochs)
    steps = bucket.valid.sum(axis=1)
    assert steps[3] == 0 and len(set(steps[:3])) > 1   # pad row, uneven
    clients_in_turn(fam, False)
    lanes = _program(fam, params, bucket, x, y)
    clients_in_turn(fam, True)
    rows = fl_batch.resident(x, fam)
    assert rows.shape == (len(x), x[0].size)
    turn = _program(fam, params, bucket, rows, y)
    for a, b in zip(jax.tree.leaves(turn[0]), jax.tree.leaves(lanes[0])):
        assert a.shape == b.shape and a.shape[0] == 4
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=0)
        assert not a[3].any()
    np.testing.assert_allclose(turn[1], lanes[1], atol=1e-6, rtol=1e-6)
    assert turn[1][3] == 0.0 and (turn[1][:3] > 0).all()


def test_half_batch_changes_clients_in_turn(clients_in_turn):
    """The benchmark's half-batch fault wraps ``_bucket_program`` by its
    call; in turn, the halved batches change every real client's delta."""
    x, y = _data()
    fam, params = _family_params("mlp")
    bucket = _one_bucket(*IN_TURN_CASES["one_epoch"][::-1])
    clients_in_turn(fam, True)

    def half_batch(sub, x_all, y_all, gather, valid, **kw):
        return fl_batch._bucket_program(
            sub, x_all, y_all, gather[..., :gather.shape[-1] // 2], valid,
            **kw)

    full = _program(fam, params, bucket, x, y)
    half = _program(fam, params, bucket, x, y, program=half_batch)
    for a, b in zip(jax.tree.leaves(full[0]), jax.tree.leaves(half[0])):
        assert all(np.abs(a[r] - b[r]).max() > 0 for r in range(3))
        assert not b[3].any()


def test_clients_in_turn_reuse_program_and_count_executed_slots(
        clients_in_turn):
    """A client's step count is data: another cohort of other lengths in
    the same (P_pad, T_pad) compiles nothing, and only real steps run."""
    x, y = _data()
    fam, params = _family_params("mlp")
    clients_in_turn(fam, True)
    kw = dict(epochs=2, batch=32, lr=0.05, family=fam, fleet_steps=8)

    def cohort(sizes):
        starts = np.cumsum((0,) + sizes)
        parts = [np.arange(a, b) for a, b in zip(starts[:-1], starts[1:])]
        ids = list(range(len(parts)))
        seeds = [fl_client.client_update_seed(0, 1, i) for i in ids]
        before = dict(fl_batch.COUNTERS)
        res = fl_batch.run_cohort("drfl", params, x, y, parts, ids,
                                  [2] * len(ids), seeds, **kw)
        return res, {k: v - before[k] for k, v in fl_batch.COUNTERS.items()}

    fl_batch.reset_counters()
    # 4, 2 and 6 steps, then 8, 4 and 2 (a wrap-around client), both laid
    # out at the fleet's 8 steps: [4, 8]
    first, c1 = cohort((64, 40, 96))
    with compile_guard(fl_batch._bucket_program, counters=fl_batch.COUNTERS,
                       max_new=0):
        second, c2 = cohort((128, 70, 20))
    assert c1["compiles"] == 1
    for res, c, real in ((first, c1, 12), (second, c2, 14)):
        (b,) = res.buckets
        assert jax.tree.leaves(b.stacked_delta)[0].shape[0] == 4
        assert c["real_steps"] == c["slots"] == real
        assert c["skipped_slots"] == 4 * 8 - real
        assert c["executions"] == 1


def test_clients_in_turn_lay_buckets_out_at_fleet_steps(clients_in_turn):
    """In turn, every bucket takes the fleet's longest schedule and
    ``IN_TURN_ROWS`` rows, as many buckets a submodel as its clients fill,
    so a cohort of other clients, lengths or sizes makes no new program
    shape (pad steps and rows cost nothing); the lanes keep their own P
    and T."""
    x, y = _data()
    fam, params = _family_params("mlp")
    # 2 epochs of batch 32: 2, 2 (wrap-around) and 14 steps; no data, none;
    # then four more clients of 2 steps
    parts = [np.arange(0, 40), np.arange(40, 64), np.arange(64, 300),
             np.arange(0), *(np.arange(a, a + 40) for a in range(0, 160, 40))]
    steps = fl_batch.longest_schedule(parts, epochs=2, batch=32)
    assert steps == 14
    rows = fl_batch.IN_TURN_ROWS
    assert rows == 4

    def cohort(ids, models, **kw):
        seeds = [fl_client.client_update_seed(0, 2, i) for i in ids]
        before = dict(fl_batch.COUNTERS)
        res = fl_batch.run_cohort(
            "drfl", params, x, y, [parts[i] for i in ids], ids, models,
            seeds, epochs=2, batch=32, lr=0.05, family=fam, **kw)
        c = {k: v - before[k] for k, v in fl_batch.COUNTERS.items()}
        got = [len(b.weights) for b in res.buckets]
        assert [b.participants for b in res.buckets] == [
            [i for i, m in zip(ids, models) if m == b.model_idx][
                r:r + len(b.participants)]
            for b, r in zip(res.buckets, _chunk_starts(res.buckets))]
        # the schedule's slots, run or not, are its P_pad x T_pad
        return got, (c["slots"] + c["skipped_slots"]) // sum(got), c

    fl_batch.reset_counters()
    clients_in_turn(fam, True)
    with pytest.raises(ValueError, match="fleet_steps"):
        cohort([0, 1], [2, 2])
    got, t_pad, short = cohort([0, 1], [2, 2], fleet_steps=steps)
    assert got == [rows] and t_pad == 16
    with compile_guard(fl_batch._bucket_program, counters=fl_batch.COUNTERS,
                       max_new=0):
        _, _, long = cohort([1, 2], [2, 2], fleet_steps=steps)
        # six clients of one submodel fill two buckets of the same shape
        got, _, many = cohort([0, 1, 4, 5, 6, 7], [2] * 6, fleet_steps=steps)
    assert short["skipped_slots"] == rows * 16 - 4
    assert long["skipped_slots"] == rows * 16 - 16
    assert got == [rows, rows] and many["executions"] == 2
    assert many["skipped_slots"] == 2 * rows * 16 - 12
    got, t_pad, split = cohort([0, 1, 2], [1, 2, 2], fleet_steps=steps)
    assert got == [rows, rows] and t_pad == 16
    assert split["compiles"] == 1          # submodel 1's program alone
    clients_in_turn(fam, False)
    got, t_pad, lanes = cohort([0, 1], [2, 2], fleet_steps=steps)
    assert got == [2] and t_pad == 2 and lanes["skipped_slots"] == 0


def _chunk_starts(buckets):
    """Each bucket's first client's place among its submodel's clients."""
    seen: dict = {}
    out = []
    for b in buckets:
        out.append(seen.get(b.model_idx, 0))
        seen[b.model_idx] = out[-1] + len(b.participants)
    return out


def test_resident_set_is_rows_only_where_clients_run_in_turn(
        clients_in_turn):
    x, _ = _data()
    fam = get_family("cnn")
    np.testing.assert_array_equal(fl_batch.resident(x, fam), x)
    clients_in_turn(fam, True)
    np.testing.assert_array_equal(fl_batch.resident(x, fam),
                                  x.reshape(len(x), -1))


def test_clients_in_turn_hold_one_client_and_one_output(clients_in_turn):
    """Compiled memory at a small CNN bucket: in turn, the output is one
    stacked delta tree and the losses, and the temporaries hold one
    client's parameters, gradients and activations: less than the lanes'
    (every lane's), and less than a second stacked tree."""
    x, y = _data()
    # wide enough that a stacked tree outweighs one client's activations
    fam, params = _family_params("cnn", width=0.5)
    bucket = _one_bucket(*IN_TURN_CASES["one_epoch"][::-1], model=3)
    sub = fam.submodel_params("drfl", params, bucket.model_idx)
    p_pad = bucket.gather.shape[0]

    def memory(on):
        clients_in_turn(fam, on)
        data = fl_batch.resident(x, fam)
        return fl_batch._bucket_program.lower(
            sub, data, jnp.asarray(y), jnp.asarray(bucket.gather),
            jnp.asarray(bucket.valid), method="drfl", lr=0.05,
            family=fam).compile().memory_analysis()

    lanes, turn = memory(False), memory(True)
    leaves = jax.tree.leaves(sub)
    tree_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    # the rows of the deltas and the losses, and the output tuple's table
    # of one 8-byte pointer a buffer
    assert turn.output_size_in_bytes == (p_pad * (tree_bytes + 4)
                                         + 8 * (len(leaves) + 1))
    assert lanes.output_size_in_bytes == turn.output_size_in_bytes
    assert turn.temp_size_in_bytes < lanes.temp_size_in_bytes
    # no second stacked tree beside the output
    assert turn.temp_size_in_bytes < p_pad * tree_bytes


@pytest.mark.parametrize("mode,method", [("sync", "drfl"),
                                         ("sync", "scalefl"),
                                         ("async", "drfl")])
def test_engines_train_clients_in_turn_on_resident_rows(clients_in_turn,
                                                        mode, method):
    """Clients in turn through either engine, on the rows ``resident``
    hands the bucket programs: the per-client reference's decisions and
    accuracies, as the lanes give them."""
    cfg = _small_cfg(n_devices=8, n_rounds=2, engine_mode=mode,
                     method=method, client_executor="perclient")
    h_pc = run_simulation(cfg)
    clients_in_turn(get_family(cfg.model_family), True)
    fl_batch.reset_counters()
    h = run_simulation(dataclasses.replace(cfg, client_executor="batched"))
    assert fl_batch.COUNTERS["executions"] > 0
    assert fl_batch.COUNTERS["slots"] == fl_batch.COUNTERS["real_steps"]
    assert h["participants"] == h_pc["participants"]
    assert h["energy"] == h_pc["energy"]
    np.testing.assert_allclose(h["acc_mean"], h_pc["acc_mean"], atol=0.06)
