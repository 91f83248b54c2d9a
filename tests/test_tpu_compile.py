"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: blocks not aligned to
the tiling, kernels over the fast-memory limit, programs that do not fit the
device.  These compiles guard the main path's kernels, the default
worker step at each benchmark cell's share shapes, the jitted training scan
at paper Case 1 width (N=40, K=13, T=1, r=1, (m, d) = (12396, 1568)) and
each job's compiled dataset encode at no chip time.

Only one process at a time may load the TPU library, and it keeps it until
it exits.  So the topology is described inside a module fixture, never at
import, and every such compile lives in this one file: under several test
workers only the worker given this file loads the library.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import field
from repro.core.protocol import CPMLConfig, compute, encode, engine
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


# Each one-chip cell's shares (N, m/K, d) and the epsilon cell's, whose 40
# shares only a v5e host holds: (c, p) as the cells run them.
CELLS = {"mnist37": (40, MK, D, 1, field.P),
         "mnist10-p30": (40, 4616, 784, 10, field.P30),
         "epsilon-p30": (40, 30770, 2000, 1, field.P30)}


def _int32_dots(hlo: str, scope: str) -> list[str]:
    """The compiled program's int32 dots (or convolutions) under ``scope``."""
    return [line for line in hlo.splitlines() if scope in line
            and re.search(r"= s32\[[^=]*\b(dot|convolution)\(", line)]


@pytest.mark.parametrize("cell, use_kernel", [
    ("mnist37", None), ("mnist10-p30", None), ("mnist37", False)],
    ids=["mnist37", "mnist10-p30", "mnist37-reference"])
def test_default_worker_step_compiles_for_v5e(one_chip, for_tpu, cell,
                                              use_kernel):
    """The worker step as the one-chip cells build it (the scan and the
    cluster round share it): on a TPU the default is the fused kernel, and
    no int32 dot is left under the worker's scope; forcing the reference
    shows what the check would find."""
    n, mk, d, c, p = CELLS[cell]
    cfg = CPMLConfig(**CASE1, c=c, p=p, use_kernel=use_kernel)

    def step(cbar, xs, ws):
        with jax.named_scope(engine.SCOPE_WORKER):
            return compute.all_worker_results(cfg, cbar, xs, ws)

    hlo = jax.jit(step).lower(
        _sds((cfg.r + 1,), jnp.int32, one_chip),
        _sds((n, mk, d), jnp.int32, one_chip),
        _sds((n, d, c, cfg.r), jnp.int32, one_chip)).compile().as_text()
    fused = use_kernel is None
    assert ("tpu_custom_call" in hlo) == fused
    assert (not _int32_dots(hlo, engine.SCOPE_WORKER)) == fused


def test_cluster_round_program_compiles_for_v5e(one_chip, for_tpu):
    """The cluster cell's per-round program (``engine._round``, the one
    ``ClusterRunner`` dispatches) at Case 1 width: the fused kernel, no
    int32 dot under the worker's scope."""
    cfg = CPMLConfig(**CASE1)
    R, i32, f32 = cfg.threshold, jnp.int32, jnp.float32
    hlo = engine._round_jit.lower(
        cfg, _sds((2,), jnp.uint32, one_chip), _sds((D, 1), f32, one_chip),
        _sds((cfg.N, MK, D), i32, one_chip), None, None,
        _sds((D, 1), f32, one_chip), _sds((R, cfg.K), i32, one_chip),
        _sds((R,), i32, one_chip), None, _sds((), f32, one_chip),
        _sds((), i32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert not _int32_dots(hlo, engine.SCOPE_WORKER)


def test_default_worker_step_shard_compiles_for_v5e_2x2(topo, for_tpu):
    """The epsilon cell's worker step on a v5e host: each chip's 10 shares
    of (30770, 2000) through the fused kernel, with no int32 dot and no
    copy of the shares before it."""
    n, mk, d, c, p = CELLS["epsilon-p30"]
    cfg = CPMLConfig(**CASE1, c=c, p=p, backend="shard")
    mesh = auto_mesh((4,), (cfg.mesh_axis,), devices=topo.devices)
    rep = NamedSharding(mesh, PartitionSpec())
    mine = NamedSharding(mesh, PartitionSpec(cfg.mesh_axis))

    def step(cbar, xs, ws):
        with jax.named_scope(engine.SCOPE_WORKER):
            return compute.all_worker_results(cfg, cbar, xs, ws)

    with jax.set_mesh(mesh):
        compiled = jax.jit(step).lower(
            _sds((cfg.r + 1,), jnp.int32, rep),
            _sds((n, mk, d), jnp.int32, mine),
            _sds((n, d, c, cfg.r), jnp.int32, mine)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "all-gather" in hlo
    assert not _int32_dots(hlo, engine.SCOPE_WORKER)
    assert compiled.memory_analysis().temp_size_in_bytes < 1e8


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
