"""The benchmark's only door into the program under test.

Everything here calls the program's public entries: ``repro.core.protocol``,
``repro.cluster`` and ``repro.launch.mesh.auto_mesh``. A configuration file
names no implementation switch: the program's defaults decide the path, so
a later change of a default is measured as it ships. The one exception is
where the layout is the cell's: a cell on several chips spreads the workers
over them with the shard backend.
"""
from __future__ import annotations

import contextlib
import dataclasses
import pathlib
import sys
import time

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import protocol  # noqa: E402

# implementation switches and layout: never read from a configuration
NOT_CONFIG = ("backend", "mesh_axis", "use_kernel")


def coded_config(config: dict, chips: int, traffic: dict | None = None):
    """The program's ``CPMLConfig`` from the configuration's sizes and any
    protocol setting the traffic mix names (such as ``batch_rows``)."""
    names = {f.name for f in dataclasses.fields(protocol.CPMLConfig)}
    given = {**config, **(traffic or {}).get("protocol", {})}
    bad = set((traffic or {}).get("protocol", {})) - names
    if bad or any(k in given for k in NOT_CONFIG):
        raise ValueError(f"not a protocol setting a cell may name: "
                         f"{sorted(bad | (set(given) & set(NOT_CONFIG)))}")
    cfg = protocol.CPMLConfig(**{k: v for k, v in given.items()
                                 if k in names})
    if chips > 1:
        cfg = dataclasses.replace(cfg, backend="shard")
    return cfg


def mesh(cfg, devices):
    from repro.launch.mesh import auto_mesh
    return auto_mesh((len(devices),), (cfg.mesh_axis,), devices=devices)


@contextlib.contextmanager
def layout(cfg, devices):
    """The cell's layout: a mesh over its chips for the shard backend."""
    if cfg.backend != "shard":
        yield
        return
    with jax.set_mesh(mesh(cfg, devices)):
        yield


def probe_programs(cfg):
    """The worker polynomial and the decode of one round, each jitted under
    the stable name the trace reduction looks for."""

    def bench_worker_step(cbar, x_shares, w_shares):
        return protocol.all_worker_results(cfg, cbar, x_shares, w_shares)

    def bench_decode_step(results, dmat):
        return protocol.decode_gradient(cfg, results, dmat)

    return jax.jit(bench_worker_step), jax.jit(bench_decode_step)


class Probes:
    """The layers timed from outside the round: one round's worker
    polynomial and decode, each as a jitted program with a stable name, and
    the dataset encode as a training job's set-up runs it."""

    def __init__(self, cfg, devices, x, key):
        self.cfg, self.devices, self.x = cfg, devices, x
        kx, kw, kq = jax.random.split(key, 3)
        self.key_x = kx
        with layout(cfg, devices):
            x_shares, _ = protocol.encode_dataset(cfg, kx, x)
            w2 = 0.01 * jax.random.normal(kw, (x.shape[1], cfg.c))
            w_shares = protocol.encode_weights(cfg, kq, w2)
            if cfg.backend == "shard":
                from jax.sharding import NamedSharding, PartitionSpec
                spec = NamedSharding(mesh(cfg, devices),
                                     PartitionSpec(cfg.mesh_axis))
                x_shares = jax.device_put(x_shares, spec)
                w_shares = jax.device_put(w_shares, spec)
            self.args = (jnp.asarray(protocol.poly_coeffs(cfg)), x_shares,
                         w_shares)
            self.worker, self.decode = probe_programs(cfg)
            results = jax.block_until_ready(self.worker(*self.args))
            order = np.arange(cfg.threshold)
            self.dec_args = (results[: cfg.threshold],
                             protocol.make_decode_matrix(cfg, order))
            jax.block_until_ready(self.decode(*self.dec_args))

    def run_devices(self, calls: int) -> dict[str, int]:
        """Run each program ``calls`` times, one at a time."""
        with layout(self.cfg, self.devices):
            for _ in range(calls):
                jax.block_until_ready(self.worker(*self.args))
            for _ in range(calls):
                jax.block_until_ready(self.decode(*self.dec_args))
        return {"bench_worker_step": calls, "bench_decode_step": calls}

    def dataset_encode_s(self, min_s: float = 0.25, min_calls: int = 3
                         ) -> float:
        """Mean host seconds of the program's eager dataset encode."""
        calls, t0 = 0, time.perf_counter()
        while calls < min_calls or time.perf_counter() - t0 < min_s:
            jax.block_until_ready(
                protocol.encode_dataset(self.cfg, self.key_x, self.x)[0])
            calls += 1
        return (time.perf_counter() - t0) / calls
