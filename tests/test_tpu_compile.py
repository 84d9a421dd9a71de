"""AOT compiles of the Pallas kernels, and of the CT fold launch, for a
described TPU v5e.

Interpret mode (tests/test_pallas.py) checks what the kernels compute;
only Mosaic says whether they compile: it refuses slices off the tiling
and more scoped VMEM than a kernel may use, which interpret mode never
sees. Each test compiles one kernel at the shapes the streamed bodies
really pass at a catalogue width — captured by tracing the body with
the kernel swapped for a shape recorder — for a v5e that is described,
not attached. Nothing runs; a pass is not a chip run.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and xdist workers all
import this file (see the on-chip-measurement guide, section 2).
"""

import os

import jax
import jax.numpy as jnp
import pytest

# (config, facets in the program): 9 on one chip; the 64k facet mesh
# pads 9 to 12 facets, three per chip of a 2x2 host
COLPASS_CASES = [
    ("32k[1]-n16k-512", 9),
    ("64k[1]-n32k-1k", 9),
    ("64k[1]-n32k-1k", 3),
    ("128k[1]-n32k-512", 9),
]
FOLD_CONFIGS = ["32k[1]-n16k-512", "64k[1]-n32k-1k", "128k[1]-n32k-512"]


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 (the TPU library loads in this worker)."""
    from jax.experimental import topologies

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture
def one_chip(topo):
    """One described chip, compiled for as the chip runs: 32-bit (Mosaic
    refuses the i64 index maps x64 mode makes) and with JAX's persistent
    cache off (a compile for a described chip is written but can never
    be read back without one). Function-scoped: other files' tests may
    run between these on the same worker."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()


def _core(name):
    from swiftly_tpu import SWIFT_CONFIGS, SwiftlyConfig

    params = dict(SWIFT_CONFIGS[name])
    params.setdefault("fov", 1.0)
    return params, SwiftlyConfig(
        backend="planar", dtype=jnp.float32, **params
    ).core


def _recorder(kernel, calls):
    """Stand-in for ``kernel`` that records its argument shapes and
    returns zeros of the real kernel's output shapes."""

    def record(*args, **kwargs):
        calls.append((args, kwargs))
        outs = jax.eval_shape(lambda *a: kernel(*a, **kwargs), *args)
        return tuple(jnp.zeros(o.shape, o.dtype) for o in outs)

    return record


def _compile(fn, sharding, args, **kwargs):
    specs = [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        for a in args
    ]
    text = jax.jit(lambda *a: fn(*a, **kwargs)).lower(*specs).compile()
    assert "tpu_custom_call" in text.as_text()


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("name,F", COLPASS_CASES)
def test_colpass_compiles_at_streamed_shapes(one_chip, monkeypatch, name,
                                             F, direction):
    from swiftly_tpu.ops import pallas_kernels
    from swiftly_tpu.parallel import streamed

    params, core = _core(name)
    yB, xA, m = params["yB_size"], params["xA_size"], core.xM_yN_size
    S = -(-params["N"] // xA)  # subgrids in one column of the full cover
    calls = []
    monkeypatch.setattr(
        pallas_kernels, "colpass_pallas",
        _recorder(pallas_kernels.colpass_pallas, calls),
    )
    i32 = jnp.int32
    if direction == "forward":
        body = streamed._column_pass_fwd_pallas_fn(core, xA)
        jax.eval_shape(
            body, _sds((F, m, yB, 2)), _sds((F,), i32), _sds((F,), i32),
            _sds((S, 2), i32), _sds((S, xA)), _sds((S, xA)),
        )
    else:
        body = streamed._column_pass_bwd_einsum_fn(core, yB, use_pallas=True)
        jax.eval_shape(
            body, _sds((S, xA, xA, 2)), _sds((S, 2), i32), _sds((F,), i32),
            _sds((F,), i32), _sds((F, yB)),
        )
    assert calls, "the body did not reach colpass_pallas"
    monkeypatch.undo()
    for args, kwargs in {
        (tuple(a.shape for a in args), kwargs["reduce_f"]): (args, kwargs)
        for args, kwargs in calls
    }.values():
        _compile(pallas_kernels.colpass_pallas, one_chip, args, **kwargs)


@pytest.mark.parametrize("name", FOLD_CONFIGS)
def test_bwd_fold_compiles_at_streamed_shapes(one_chip, monkeypatch, name):
    from swiftly_tpu.ops import pallas_kernels
    from swiftly_tpu.parallel import streamed

    params, core = _core(name)
    yB, m, F = params["yB_size"], core.xM_yN_size, 9
    R = 2 * m  # rows of one fold group of two columns
    calls = []
    monkeypatch.setattr(
        pallas_kernels, "bwd_fold_pallas",
        _recorder(pallas_kernels.bwd_fold_pallas, calls),
    )
    # bypass the lru_cache: cores hash by value, so a cached body would
    # keep the recorder for later callers
    fold = streamed._bwd_sampled_fold_fn.__wrapped__(core, use_pallas=True)
    jax.eval_shape(
        fold, _sds((F, yB, yB, 2)), _sds((F, R, yB, 2)),
        _sds((F,), jnp.int32), _sds((R,), jnp.int32), _sds((), jnp.int32),
    )
    assert calls, "the fold did not reach bwd_fold_pallas"
    monkeypatch.undo()
    args, kwargs = calls[0]
    _compile(pallas_kernels.bwd_fold_pallas, one_chip, args, **kwargs)


@pytest.mark.parametrize("g", [1, 2])
def test_ct_fold_compiles_in_place_at_32k(one_chip, g):
    """One CT fold launch at the 32k round trip's calls (9 facets, g
    columns) updates the donated 9.1 GB accumulator in place, its
    transients inside the plan's reserve: a body that relayouts the
    whole accumulator does not fit the chip and fails to compile."""
    from swiftly_tpu.parallel import streamed
    from swiftly_tpu.plan.model import DEFAULT_RESERVE_BYTES

    params, core = _core("32k[1]-n16k-512")
    yB, m, F = params["yB_size"], core.xM_yN_size, 9
    offs = tuple(c * params["xA_size"] for c in range(g))
    Q, P, kmax, tab = streamed._ct_fold_tables(core, offs)
    W = streamed._ct_fold_width(
        yB, streamed._ct_column_bytes(core, F, yB), DEFAULT_RESERVE_BYTES
    )
    assert W < yB and yB % W == 0 and W % 128 == 0
    fold = jax.jit(
        streamed._bwd_ct_fold_fn(core, Q, P, kmax, W), donate_argnums=0
    )
    acc = jax.ShapeDtypeStruct((F, yB, yB, 2), jnp.float32, sharding=one_chip)
    args = [
        acc,
        jax.ShapeDtypeStruct((F, g * m, yB, 2), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((F,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct(tab.shape, jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ]
    mem = fold.lower(*args).compile().memory_analysis()
    assert mem.alias_size_in_bytes == F * yB * yB * 2 * 4
    assert mem.temp_size_in_bytes <= DEFAULT_RESERVE_BYTES


@pytest.mark.parametrize("n", [512, 1024])
def test_cmatmul_compiles(one_chip, n):
    """The direct planar DFT (n <= 1024) over a column pass's rows."""
    from swiftly_tpu.ops.pallas_kernels import cmatmul_pallas

    rows, mat = _sds((16384, n)), _sds((n, n))
    _compile(cmatmul_pallas, one_chip, (rows, rows, mat, mat))


@pytest.mark.parametrize("bucket", [2, 4096])
def test_degrid_kernel_compiles(one_chip, bucket):
    """The smallest and largest sample bucket at the default support."""
    from swiftly_tpu.vis.degrid import _degrid_fn
    from swiftly_tpu.vis.kernel import VisKernel

    W = VisKernel().support
    i32 = jnp.int32
    _compile(
        _degrid_fn.__wrapped__(W, True), one_chip,
        (_sds((1024, 1024)), _sds((1024, 1024)), _sds((bucket,), i32),
         _sds((bucket,), i32), _sds((bucket, W)), _sds((bucket, W))),
    )


def test_fused_slab_step_keeps_each_stage_scope_for_the_chip(one_chip):
    """The facet-slab plan's fused step for point-component facets, in
    slabs of one facet as the 128k plan runs it: compiled for the chip,
    the zero fill of the synthesised plane stays under
    ``swiftly/fwd.facet_synth`` (the chip's compiler rebuilds an
    unguarded fill with no scope) and the column-pass kernel under
    ``swiftly/fwd.slab_step``."""
    from conftest import compiled_stages
    from swiftly_tpu.parallel import streamed

    params, core = _core("1k[1]-n512-512")
    yB, xA, xM = params["yB_size"], params["xA_size"], params["xM_size"]
    G, S, m = 2, -(-params["N"] // xA), core.xM_yN_size

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = jnp.int32
    args = [sds((1, G, S, xM, xM, 2)), sds((1,), i32), sds((1,), i32),
            sds((1,), i32), sds((1,)), sds((1,), i32), sds((G * m,), i32),
            sds((1,), i32), sds((1,), i32), sds((1, G, S, 2), i32)]
    stages, entry = compiled_stages(streamed._fused_sparse_slab_step_j(
        core, xA, G, 1, yB, "pallas").lower(*args).compile())
    fill = [k for k, (shape, op, _) in entry.items()
            if op == "broadcast" and shape in (f"1,{yB},{yB}", f"{yB},{yB}")]
    kernel = [k for k, (_, op, line) in entry.items()
              if op == "custom-call" and "colpass_pallas" in line]
    assert fill and kernel
    assert {stages[k] for k in fill} == {"swiftly/fwd.facet_synth"}
    assert {stages[k] for k in kernel} == {"swiftly/fwd.slab_step"}
