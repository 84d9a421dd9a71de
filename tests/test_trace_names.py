"""The names a device trace finds the program's work by, checked in the
lowered HLO on the CPU: each Pallas kernel's ``name=`` (the kernel name
Mosaic compiles and a scope of its own in the instruction's
``op_name``, lowered for the TPU without one attached) and the
``swiftly/mesh.psum`` scope around the mesh's psum."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from swiftly_tpu.ops import pallas_kernels as pk


def _shapes(*shapes):
    return [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]


KERNELS = {
    "cmatmul_pallas": (
        pk.cmatmul_pallas, _shapes((256, 256), (256, 256), (256, 256),
                                   (256, 256))),
    "bwd_fold_pallas": (
        pk.bwd_fold_pallas, _shapes((256, 256), (256, 256), (256, 256),
                                    (256, 256), (256, 256), (256, 256),
                                    (256, 1))),
    "colpass_pallas": (
        pk.colpass_pallas, _shapes((2, 256, 128), (2, 256, 128),
                                   (1, 2, 128, 256), (1, 2, 128, 256),
                                   (2, 256, 256), (2, 256, 256))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_kernels_carry_their_names(name):
    fn, args = KERNELS[name]
    with jax.enable_x64(False):
        lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text(debug_info=True)
    assert f'kernel_name = "{name}"' in text
    assert re.search(rf'["/]{name}/pallas_call"', text)


def test_the_mesh_psum_has_a_scope_of_its_own():
    from jax.sharding import Mesh, PartitionSpec as P

    from swiftly_tpu.parallel.sharded import collective_sum

    mesh = Mesh(np.array(jax.devices()[:4]), ("facet",))

    def lowered(collective):
        fn = jax.shard_map(
            functools.partial(collective_sum, axis_name="facet",
                              collective=collective, n_shards=4),
            mesh=mesh, in_specs=P("facet"), out_specs=P(),
            check_vma=collective == "psum",
        )
        return jax.jit(fn).lower(jnp.ones((8, 128), jnp.float32)).as_text(
            debug_info=True)

    psum = lowered("psum")
    assert "all_reduce" in psum and "swiftly/mesh.psum" in psum
    ring = lowered("ring")
    assert "swiftly/mesh.ring_step" in ring
    assert "swiftly/mesh.psum" not in ring
