"""Compile-only checks of the Pallas kernels for a described TPU v5e.

Nothing runs on a chip: each test lowers and compiles a kernel at the
shapes the main path uses, against a v5e topology described by the
installed TPU compiler.  That catches what interpret mode cannot — block
shapes that break the (8, 128) tiling and tiles that overflow VMEM — at no
chip time.  The topology is described inside a module fixture (never at
import), so only the test worker that runs this file loads the TPU
library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.layer_agg import layer_agg_op
from repro.kernels.rmsnorm import rmsnorm_op

# [N, R, seg]: N aggregated client rows of one round over a stack template
# of R rows.  The paper-width ResNet-18 (width_mult=1.0, hw=32) has 11,084
# rows (11,088 once aligned; the unaligned count checks the pad path), the
# width_mult=1.0 transformer 782.
LAYER_AGG_SHAPES = [(32, 11084, 1024), (32, 11088, 1024), (256, 784, 1024),
                    # past MAX_CLIENT_BLOCK: the client axis is tiled
                    (1024, 64, 1024)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from a persistent
    # cache, so keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled, name):
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert name in text


@pytest.mark.parametrize("N,R,D", LAYER_AGG_SHAPES)
def test_layer_agg_compiles(one_chip, N, R, D):
    c = _compile(functools.partial(layer_agg_op, interpret=False), one_chip,
                 ((N, R, D), jnp.float32), ((N, R), jnp.float32),
                 ((N,), jnp.float32))
    _assert_kernel(c, "layer_agg")


@pytest.mark.parametrize("rows", [
    32 * 32,       # transformer width_mult=1.0: batch 32 x sequence 32
    32,            # its exit heads normalise the last position only
    7,             # odd: rows pad to the 8-row tile
])
def test_rmsnorm_compiles(one_chip, rows):
    c = _compile(functools.partial(rmsnorm_op, interpret=False), one_chip,
                 ((rows, 128), jnp.float32), ((128,), jnp.float32))
    _assert_kernel(c, "rmsnorm")


@pytest.mark.parametrize("q_shape,kv_shape,causal,block_q,block_k", [
    # transformer width_mult=1.0: batch 32, sequence 32, 4 heads of 32
    ((32, 32, 4, 32), (32, 32, 4, 32), True, 32, 32),
    # set mixer: 4 seed queries pooling over 65,536 agents
    ((16, 4, 1, 32), (16, 65536, 1, 32), False, 4, 128),
])
def test_flash_attention_compiles(one_chip, q_shape, kv_shape, causal,
                                  block_q, block_k):
    fn = functools.partial(flash_attention, causal=causal, block_q=block_q,
                           block_k=block_k, interpret=False)
    c = _compile(fn, one_chip, (q_shape, jnp.float32),
                 (kv_shape, jnp.float32), (kv_shape, jnp.float32))
    _assert_kernel(c, "flash_attention")


def test_bucket_program_in_turn_holds_no_copy_of_the_training_set(
        one_chip, monkeypatch):
    """The CNN's bucket program, clients in turn, on the training set as
    ``fl.batch.resident`` holds it (rows): its temporaries hold one
    client's training and no copy of the set, which an image-shaped set
    (or a conversion hoisted out of the step loop) would add."""
    from repro.fl import batch as fl_batch
    from repro.models.family import get_family
    fam = get_family("cnn")
    monkeypatch.setattr(fam, "bucket_clients_in_turn", lambda: True)
    params = jax.eval_shape(lambda: fam.init(
        jax.random.PRNGKey(0), 10, width_mult=0.25, hw=32))
    sub = jax.eval_shape(lambda p: fam.submodel_params("drfl", p, 3), params)
    n, p_pad, t_pad, batch = 32768, 4, 8, 32

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one_chip)

    args = (jax.tree.map(lambda a: shape(a.shape, a.dtype), sub),
            shape((n, 32 * 32 * 3), jnp.float32), shape((n,), jnp.int32),
            shape((p_pad, t_pad, batch), jnp.int32),
            shape((p_pad, t_pad), jnp.float32))
    fl_batch._bucket_program.clear_cache()
    try:
        mem = fl_batch._bucket_program.lower(
            *args, method="drfl", lr=0.05, family=fam).compile(
            ).memory_analysis()
    finally:
        fl_batch._bucket_program.clear_cache()
    set_bytes = n * 32 * 32 * 3 * 4
    tree_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(sub))
    assert mem.output_size_in_bytes >= p_pad * tree_bytes
    # a bfloat16 copy of the set alone is half its bytes
    assert mem.temp_size_in_bytes < set_bytes // 4, mem


def test_stacked_aggregation_averages_the_stack_in_place(one_chip):
    """The CNN's averaging program, with its ``layer_agg`` kernel, over a
    round's stacked client rows: it makes no copy of the stack, and
    stacking a bucket's rows into it updates the stack in place."""
    from repro.fl import server as fl_server
    from repro.models.family import get_family
    fam = get_family("cnn")
    params = jax.eval_shape(lambda: fam.init(
        jax.random.PRNGKey(0), 10, width_mult=0.25, hw=32))
    t = fam.stack_template(params)
    n, p = 16, 8

    def shape(s, d=jnp.float32):
        return jax.ShapeDtypeStruct(s, d, sharding=one_chip)

    gp = jax.tree.map(lambda a: shape(a.shape, a.dtype), params)
    stack, mask = shape((n, t.n_rows, t.seg)), shape((n, t.n_rows))
    stack_bytes = n * t.n_rows * t.seg * 4
    agg = fl_server._stacked_agg_program.lower(
        gp, stack, mask, shape((n,)), shape((n,)), family=fam,
        server_lr=1.0, any_stale=False, use_kernel=True,
        interpret=False).compile()
    _assert_kernel(agg, "layer_agg")
    assert agg.memory_analysis().temp_size_in_bytes < stack_bytes // 4
    delta = jax.tree.map(
        lambda a: shape((p,) + a.shape, a.dtype),
        jax.eval_shape(lambda q: fam.submodel_params("drfl", q, 3), params))
    rows = fl_server._stack_bucket_rows.lower(
        stack, mask, shape((n,), jnp.bool_), gp, delta, shape((), jnp.int32),
        shape((), jnp.int32), family=fam, model_idx=3, validate=True,
        mag_cap=fl_server.DELTA_MAG_CAP).compile().memory_analysis()
    assert rows.alias_size_in_bytes >= stack_bytes
    assert rows.temp_size_in_bytes < stack_bytes // 4
