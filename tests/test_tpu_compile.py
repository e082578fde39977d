"""Compile the main path's kernels for a TPU v5e that is described, not
attached.

``FleetStream`` launches the six segmenter kernels with their carries at
deployment widths; the serving and store paths reconstruct through the
same kernel launcher.  Interpret mode on the CPU cannot see what the
chip's compiler refuses (sub-32-bit rows at a dynamic sublane index,
float iotas, VMEM overruns), so every such launch is compiled here for
one chip of a ``v5e:2x2`` topology at 4,096 lanes x 1,152 steps, and the
four-chip fleet pipeline for the whole topology.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.  Where it cannot
be described the fixture skips these tests.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

S, T = 4096, 1152           # lanes x time steps of one launch
EPS = 1.0
SEGMENTERS = ("angle", "swing", "disjoint", "linear", "continuous",
              "mixed")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_lowering(monkeypatch):
    """Lower Pallas kernels as Mosaic calls (the CPU backend would pick
    interpret mode) and keep these compiles out of the persistent cache,
    which cannot read them back without a chip."""
    from repro.kernels import common
    monkeypatch.setattr(common, "interpret_mode", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _kernel(method):
    from repro.kernels import ops
    kernel_fn, init_carry, _, windowed = ops._STREAM_KERNELS[method]
    return kernel_fn, init_carry, windowed


def _compile(fn, *args):
    """Compile ``fn`` afresh (no trace shared with CPU-side callers)."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("max_run", [127, 256])
@pytest.mark.parametrize("method", SEGMENTERS)
def test_streaming_segmenter_kernel_compiles(topo, one_chip, tpu_lowering,
                                             method, max_run):
    """The launch ``StreamingSegmenter`` makes for a 1,152-step push."""
    from repro.core.jax_pla import check_window
    from repro.kernels.ops import DEFERRED_KERNELS
    kernel_fn, init_carry, windowed = _kernel(method)
    window = check_window(max_run, None) if windowed else None
    rows = jax.eval_shape(lambda: init_carry(S, window)).shape[0]
    kw = dict(eps=EPS, max_run=max_run)
    if windowed:
        kw["window"] = window
    if method in DEFERRED_KERNELS:
        kw["t_stop"] = T
    else:
        kw["t_real"] = -1
    raw = kernel_fn.__wrapped__
    compiled = _compile(
        lambda y_t, carry: raw(y_t, carry=carry, **kw),
        jax.ShapeDtypeStruct((T, S), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((rows, S), jnp.float32, sharding=one_chip))
    mem = compiled.memory_analysis()
    # Events plus the carry out: nothing else is kept in HBM.
    assert mem.temp_size_in_bytes == 0
    assert mem.output_size_in_bytes >= 3 * T * S * 4


@pytest.mark.parametrize("name", ["reconstruct", "reconstruct_error"])
def test_reconstruct_kernel_compiles(one_chip, tpu_lowering, name):
    from repro.kernels import reconstruct
    fn = {"reconstruct": reconstruct.reconstruct_pallas,
          "reconstruct_error": reconstruct.reconstruct_error_pallas}[name]
    arrays = [jax.ShapeDtypeStruct((T, S), jnp.int32, sharding=one_chip)]
    arrays += [jax.ShapeDtypeStruct((T, S), jnp.float32, sharding=one_chip)
               ] * (3 if name == "reconstruct_error" else 2)
    _compile(fn.__wrapped__, *arrays)


@pytest.mark.parametrize("method", ["continuous", "mixed"])
def test_deferred_flush_compiles(one_chip, tpu_lowering, method):
    """The jnp flush ``StreamingSegmenter.finish`` runs from the carry."""
    from repro.kernels.ops import DEFERRED_KERNELS
    _, init_carry, _, flush = DEFERRED_KERNELS[method]
    rows = jax.eval_shape(lambda: init_carry(S, 256)).shape[0]
    carry = jax.ShapeDtypeStruct((rows, S), jnp.float32, sharding=one_chip)
    jax.jit(functools.partial(flush, eps=EPS, max_run=256, w=256,
                              t_last=T - 1)).lower(carry).compile()


@pytest.mark.parametrize("method,protocol", [("angle", "singlestream"),
                                             ("swing", "implicit")])
def test_four_chip_fleet_pipeline_compiles(topo, tpu_lowering, method,
                                           protocol):
    """``fleet_point_metrics``' shard_map over a four-chip mesh: the
    segmenter, descriptors and metrics per shard, psum/pmean across."""
    from repro.compat import sharding as cs
    from repro.sharding.fleet import FLEET_AXIS, _fleet_pipeline
    mesh = cs.make_mesh((4,), (FLEET_AXIS,), devices=topo.devices[:4])
    rows = NamedSharding(mesh, P(FLEET_AXIS, None))
    fn = _fleet_pipeline(mesh, method, protocol, "joint" if method ==
                         "swing" else "disjoint", 256, 127)
    with cs.use_mesh(mesh):
        compiled = fn.lower(
            jax.ShapeDtypeStruct((S, T), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((S,), jnp.float32,
                                 sharding=NamedSharding(mesh, P(FLEET_AXIS)))
        ).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
