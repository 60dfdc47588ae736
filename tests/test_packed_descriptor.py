"""The tick's descriptor is one host buffer (PR 38; DESIGN.md §3, "How a
dispatch reaches four devices").

``blank_desc`` makes ONE ``u8[B, W]`` buffer, a row a session, and hands the
fill its ten fields as views into it; the program's first step,
``Descriptor.unpack``, gives them back with the shapes and dtypes the views
have.  Here: random values written through the views come back from the
device bit for bit, for each configuration's input shape, dtype and burst,
on one device and over the four-device virtual CPU mesh of
``tests/conftest.py``, where each shard unpacks its own rows.
"""

import importlib
import json
from pathlib import Path

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec

from ggrs_tpu.parallel import make_mesh
from ggrs_tpu.parallel.session_pool import blank_desc

REPO = Path(__file__).resolve().parents[1]
SHARDS = 4
SESSIONS = 8


def _config_inputs(name):
    config = json.loads((REPO / "benchmark" / "configs" / f"{name}.json").read_text())
    adapter = importlib.import_module(f"benchmark.adapters.{config['adapter']}")
    example = adapter.example_inputs(config)
    return int(config["max_burst"]), example.shape, example.dtype


# the five configurations' (burst, input shape, input dtype), and one input
# wider than a byte, whose field the program bitcasts back
LAYOUTS = {
    name: (lambda name=name: _config_inputs(name))
    for name in sorted(p.stem for p in (REPO / "benchmark" / "configs").glob("*.json"))
}
LAYOUTS["float32-2x3"] = lambda: (5, (2, 3), np.dtype(np.float32))


def _random_fill(desc, seed):
    rng = np.random.default_rng(seed)
    for name in desc:
        view = desc[name]
        if view.dtype == np.bool_:
            view[...] = rng.random(view.shape) < 0.5
        elif view.dtype.kind == "f":
            view[...] = rng.standard_normal(view.shape)
        else:
            info = np.iinfo(view.dtype)
            view[...] = rng.integers(
                info.min, info.max, view.shape, dtype=view.dtype, endpoint=True)


@pytest.mark.parametrize("shards", [1, SHARDS])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_what_the_views_hold_the_program_unpacks_bit_for_bit(layout, shards):
    if len(jax.devices()) < shards:
        pytest.skip("needs the virtual CPU mesh of tests/conftest.py")
    burst, shape, dtype = LAYOUTS[layout]()
    desc = blank_desc(SESSIONS, burst, shape, dtype)
    assert list(desc) == [
        "pre_frame", "load_frame", "postload_frame", "n_adv", "save_frame",
        "inputs", "pre_save", "do_load", "postload_save", "save_mask"]
    # one buffer, a row a session, and every field a view into it
    (packed,) = jax.tree_util.tree_leaves(desc)
    assert packed is desc.packed and packed.dtype == np.uint8
    assert packed.shape == (SESSIONS, desc.row.itemsize)
    assert all(np.shares_memory(desc[name], packed) for name in desc)
    _random_fill(desc, seed=shards)

    unpack = lambda d: d.unpack()  # noqa: E731
    if shards > 1:
        mesh = make_mesh(shards)
        spec = PartitionSpec(tuple(mesh.axis_names))
        unpack = jax.shard_map(unpack, mesh=mesh, in_specs=spec, out_specs=spec)
    got = jax.device_get(jax.jit(unpack)(desc))
    assert sorted(got) == sorted(desc)
    for name in desc:
        want = desc[name]
        assert (got[name].shape, got[name].dtype) == (want.shape, want.dtype), name
        np.testing.assert_array_equal(
            np.ascontiguousarray(got[name]).view(np.uint8),
            np.ascontiguousarray(want).view(np.uint8), name)
