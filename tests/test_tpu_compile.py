"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: blocks not aligned to
the tiling, kernels over the fast-memory limit, programs that do not fit the
device.  These compiles guard the main path's kernels, the jitted
training scan at paper Case 1 width (N=40, K=13, T=1, r=1,
(m, d) = (12396, 1568)) and each job's compiled dataset encode at no chip
time.

Only one process at a time may load the TPU library, and it keeps it until
it exits.  So the topology is described inside a module fixture, never at
import, and every such compile lives in this one file: under several test
workers only the worker given this file loads the library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import field
from repro.core.protocol import CPMLConfig, encode, engine
from repro.kernels import coded_grad, modmatmul
from repro.launch.mesh import auto_mesh

CASE1 = dict(N=40, K=13, T=1, r=1)
M, D = 12396, 1568
MK = -(-M // CASE1["K"])          # rows per part after padding: 954
ITERS = 5


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler here: nothing to guard
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_tpu(monkeypatch):
    """Compile as the chip would: the kernels pick interpret mode from
    jax.default_backend(), which is the CPU in this process, so steer them
    to Mosaic.  The persistent cache is off, since an entry compiled for a
    described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("c, r, p", [(1, 1, field.P), (10, 1, field.P),
                                     (10, 2, field.P30)],
                         ids=["c1", "c10", "c10-r2-P30"])
def test_coded_grad_compiles_for_v5e(one_chip, for_tpu, c, r, p):
    fn = jax.jit(functools.partial(coded_grad.coded_grad_mc, p=p))
    compiled = fn.lower(_sds((MK, D), jnp.int32, one_chip),
                        _sds((D, c, r), jnp.int32, one_chip),
                        _sds((r + 1,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_modmatmul_compiles_for_v5e(one_chip, for_tpu):
    n = 1024
    fn = jax.jit(functools.partial(modmatmul.modmatmul, p=field.P))
    a = _sds((n, n), jnp.int32, one_chip)
    compiled = fn.lower(a, a).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _scan_args(cfg: CPMLConfig, sharding):
    """_train_scan's array arguments at Case 1 width, as shapes."""
    R, c = cfg.threshold, cfg.c
    i32, f32 = jnp.int32, jnp.float32
    return (_sds((D, c), f32, sharding),                 # w0
            _sds((cfg.N, MK, D), i32, sharding),         # x_shares
            _sds((cfg.K, MK, D), f32, sharding),         # xq_parts
            _sds((cfg.K, MK, c), f32, sharding),         # y_parts
            _sds((D, c), f32, sharding),                 # xty
            _sds((ITERS, 2), jnp.uint32, sharding),      # round keys
            _sds((ITERS, R, cfg.K), i32, sharding),      # decode matrices
            _sds((ITERS, R), i32, sharding),             # survivor orders
            None,                                        # full batch
            _sds((), f32, sharding), _sds((), i32, sharding),   # eta, m
            _sds((M, D), f32, sharding), _sds((M,), f32, sharding))


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["field-matmul", "kernel"])
def test_train_scan_compiles_for_v5e(one_chip, for_tpu, use_kernel):
    """The whole jitted training scan on one chip, N shares under vmap."""
    cfg = CPMLConfig(**CASE1, use_kernel=use_kernel)
    compiled = engine._train_scan.lower(
        cfg, 0, *_scan_args(cfg, one_chip)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernel
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_train_scan_shard_compiles_for_v5e_2x2(topo, for_tpu):
    """The shard backend over the four chips of a v5e host: a block of
    N/4 = 10 shares per chip, one all_gather per round."""
    cfg = CPMLConfig(**CASE1, backend="shard")
    mesh = auto_mesh((4,), (cfg.mesh_axis,), devices=topo.devices)
    replicated = NamedSharding(mesh, PartitionSpec())
    with jax.set_mesh(mesh):
        compiled = engine._train_scan.lower(
            cfg, 0, *_scan_args(cfg, replicated)).compile()
    assert "all-gather" in compiled.as_text()


# The compiled dataset encode needed 0.73 GB of temp memory (binary) and
# 1.82 GB (10-class) when written; the eager encode it replaced, compiled
# whole, needed 2.97 and 12.25 GB.
ENCODE_TEMP_BOUND = 3e9


@pytest.mark.parametrize("c, m, d, p", [(1, M, D, field.P),
                                        (10, 60000, 784, field.P30)],
                         ids=["mnist37", "mnist10-p30"])
def test_dataset_encode_compiles_for_v5e(one_chip, for_tpu, c, m, d, p):
    """Each job's dataset encode at both scan cells' shapes; the 10-class
    shape's temp memory stays far below what the eager encode held."""
    cfg = CPMLConfig(**CASE1, c=c, p=p)
    compiled = encode._encode_dataset.lower(
        cfg, _sds((2,), jnp.uint32, one_chip),
        _sds((m, d), jnp.float32, one_chip)).compile()
    assert encode.SCOPE_ENCODE_DATASET in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < ENCODE_TEMP_BOUND


def test_sharded_dataset_encode_compiles_for_v5e_2x2(topo, for_tpu):
    """The epsilon cell's dataset encode over a v5e host, (m, d) = (400000,
    2000) at P30: the quantize leaves no full-size temporary, and each row
    block's program writes a chip's own 10 shares (2.46 GB) in place with
    well under 1 GB of temporaries (0.61 GB when written), where the
    one-program encode would hold all 40 shares on every chip."""
    cfg = CPMLConfig(**CASE1, p=field.P30, backend="shard")
    m, d = 400000, 2000
    mk = -(-m // cfg.K)
    mesh = auto_mesh((4,), (cfg.mesh_axis,), devices=topo.devices)
    rep = NamedSharding(mesh, PartitionSpec())
    mine = NamedSharding(mesh, PartitionSpec(cfg.mesh_axis))
    i32 = jnp.int32
    with jax.set_mesh(mesh):
        prep = encode._quantize_masks.lower(
            cfg, _sds((2,), jnp.uint32, rep),
            _sds((m, d), jnp.float32, rep)).compile()
        block = encode._encode_block.lower(
            cfg, encode.block_rows(cfg, mk, d),
            _sds((cfg.N, mk, d), i32, mine), _sds((cfg.K * mk, d), i32, rep),
            _sds((cfg.T, mk, d), i32, rep), _sds((), i32, rep)).compile()
    assert prep.memory_analysis().temp_size_in_bytes < 1e8
    mem = block.memory_analysis()
    # a chip's own shares, padded to the tile of the layout
    assert mem.output_size_in_bytes < 1.01 * (cfg.N // 4) * mk * d * 4
    assert mem.temp_size_in_bytes < 1e9
    assert encode.SCOPE_ENCODE_DATASET in block.as_text()
