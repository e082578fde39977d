"""Unit tests for the JAX API layer (repro.compat).

Monkeypatched spellings check that each wrapper forwards to the JAX
attribute it names, resolved at call time.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import pallas as cp
from repro.compat import sharding as cs


# ---------------------------------------------------------------------------
# compat.pallas: compiler params and interpret mode
# ---------------------------------------------------------------------------

def test_compiler_params_resolves_installed_spelling():
    from jax.experimental.pallas import tpu as pltpu
    p = cp.tpu_compiler_params(dimension_semantics=("parallel", "arbitrary"))
    assert isinstance(p, pltpu.CompilerParams)
    assert tuple(p.dimension_semantics) == ("parallel", "arbitrary")


def test_interpret_mode_on_cpu():
    if jax.default_backend() == "tpu":
        assert cp.interpret_mode() is False
    else:
        assert cp.interpret_mode() is True


def test_interpret_mode_rejects_other_backends(monkeypatch):
    """A backend that is neither the TPU nor the CPU is an error, never a
    silent fall back to interpret mode."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        cp.interpret_mode()


# ---------------------------------------------------------------------------
# compat.sharding: AxisType / abstract mesh / make_mesh / use_mesh
# ---------------------------------------------------------------------------

def test_axis_type_has_expected_members():
    for member in ("Auto", "Explicit", "Manual"):
        assert hasattr(cs.AxisType, member)
    assert cs.AxisType is jax.sharding.AxisType


def test_get_abstract_mesh_none_without_mesh():
    assert cs.get_abstract_mesh() is None


def test_get_abstract_mesh_inside_context():
    mesh = cs.make_mesh((1,), ("data",))
    with cs.use_mesh(mesh):
        info = cs.get_abstract_mesh()
        assert info is not None
        assert info.shape == {"data": 1}
        assert info.axis_names == ("data",)
        assert info.axis_types == (cs.AxisType.Auto,)
    assert cs.get_abstract_mesh() is None


def test_get_abstract_mesh_via_new_spelling(monkeypatch):
    """New-JAX path: jax.sharding.get_abstract_mesh() is used when present."""
    class _AbstractMesh:
        shape = {"data": 2, "model": 4}
        axis_types = (cs.AxisType.Auto, cs.AxisType.Manual)

    monkeypatch.setattr(jax.sharding, "get_abstract_mesh",
                        lambda: _AbstractMesh(), raising=False)
    info = cs.get_abstract_mesh()
    assert info.shape == {"data": 2, "model": 4}
    assert info.axis_types == (cs.AxisType.Auto, cs.AxisType.Manual)


def test_get_abstract_mesh_new_spelling_empty(monkeypatch):
    class _Empty:
        shape = {}
        axis_types = ()

    monkeypatch.setattr(jax.sharding, "get_abstract_mesh",
                        lambda: _Empty(), raising=False)
    assert cs.get_abstract_mesh() is None


def test_make_mesh_forwards_axis_types_when_supported(monkeypatch):
    seen = {}

    def fake_make_mesh(axis_shapes, axis_names, *, devices=None,
                       axis_types=None):
        seen["axis_types"] = axis_types
        return jax.sharding.Mesh(
            np.asarray(jax.devices()[:1]).reshape(axis_shapes), axis_names)

    monkeypatch.setattr(jax, "make_mesh", fake_make_mesh)
    cs.make_mesh((1,), ("data",))
    assert seen["axis_types"] == (cs.AxisType.Auto,)


def test_use_mesh_prefers_set_mesh(monkeypatch):
    calls = []

    def fake_set_mesh(mesh):
        calls.append(mesh)
        return contextlib.nullcontext()

    monkeypatch.setattr(jax, "set_mesh", fake_set_mesh, raising=False)
    mesh = cs.make_mesh((1,), ("data",))
    with cs.use_mesh(mesh):
        pass
    assert calls == [mesh]


def test_use_mesh_none_is_noop():
    with cs.use_mesh(None):
        pass


def test_axis_size_inside_shard_map():
    mesh = cs.make_mesh((1,), ("pod",))
    from jax.sharding import PartitionSpec as P
    sizes = []

    def f(x):
        sizes.append(cs.axis_size("pod"))
        return x

    cs.shard_map(f, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                 axis_names={"pod"}, check=False)(jnp.ones((1, 4)))
    assert sizes == [1]


# ---------------------------------------------------------------------------
# launch_segmenter: interpret-mode fallback + validation
# ---------------------------------------------------------------------------

def test_launch_segmenter_respects_interpret_mode(monkeypatch):
    """On CPU the launcher must pass interpret=True to pallas_call."""
    from repro.kernels import common
    from jax.experimental import pallas as pl

    seen = {}
    real_pallas_call = pl.pallas_call

    def spy(kernel, **kw):
        seen["interpret"] = kw.get("interpret")
        seen["grid"] = kw.get("grid")
        return real_pallas_call(kernel, **kw)

    monkeypatch.setattr(common.pl, "pallas_call", spy)

    def copy_kernel(y_ref, out_ref):
        out_ref[...] = y_ref[...]

    y = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
    out, = common.launch_segmenter(copy_kernel, y, block_s=16, block_t=8,
                                   out_dtypes=(jnp.float32,))
    assert seen["interpret"] == cp.interpret_mode()
    assert seen["grid"] == (1, 1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(y))


def test_launch_segmenter_rejects_unpadded_inputs():
    def copy_kernel(y_ref, out_ref):
        out_ref[...] = y_ref[...]

    y = jnp.zeros((7, 16), jnp.float32)
    with pytest.raises(ValueError, match="not padded"):
        common_launch(copy_kernel, y)


def common_launch(kernel, y):
    from repro.kernels.common import launch_segmenter
    return launch_segmenter(kernel, y, block_s=16, block_t=8,
                            out_dtypes=(jnp.float32,))


def test_launch_segmenter_rejects_mismatched_inputs():
    def k(a_ref, b_ref, out_ref):
        out_ref[...] = a_ref[...]

    a = jnp.zeros((8, 16), jnp.float32)
    b = jnp.zeros((16, 16), jnp.float32)
    from repro.kernels.common import launch_segmenter
    with pytest.raises(ValueError, match="differ"):
        launch_segmenter(k, (a, b), block_s=16, block_t=8,
                         out_dtypes=(jnp.float32,))


def test_launch_segmenter_reverse_time_index_map():
    """reverse_time=True hands blocks to the kernel in reverse time order."""
    from repro.kernels.common import launch_segmenter
    from jax.experimental import pallas as pl

    def stamp_kernel(y_ref, out_ref):
        # Record the sequential grid index; with the reversed index map the
        # *last* time block is written by grid step 0.
        out_ref[...] = jnp.full_like(
            y_ref[...], pl.program_id(1).astype(jnp.float32))

    y = jnp.zeros((16, 16), jnp.float32)
    out, = launch_segmenter(stamp_kernel, y, block_s=16, block_t=8,
                            out_dtypes=(jnp.float32,), reverse_time=True)
    out = np.asarray(out)
    assert (out[:8] == 1.0).all() and (out[8:] == 0.0).all()


def test_no_direct_version_dependent_refs_outside_compat():
    """Policy check (mirrors the PR acceptance grep): version-dependent
    attribute spellings appear only under repro/compat/."""
    import pathlib
    import re
    root = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    pat = re.compile(
        r"pltpu\.(TPU)?CompilerParams"
        r"|jax\.sharding\.(get_abstract_mesh|AxisType)"
        r"|jax\.(set_mesh|shard_map)\b"
        r"|jax\.make_mesh\(")
    offenders = []
    for py in root.rglob("*.py"):
        if "compat" in py.parts:
            continue
        for i, line in enumerate(py.read_text().splitlines(), 1):
            if pat.search(line):
                offenders.append(f"{py}:{i}: {line.strip()}")
    assert not offenders, "\n".join(offenders)
