"""Compiles for a described TPU v5e chip: every `kernels.ops` wrapper with
`interpret=False` at real sizes, and the granite-3-8b serving decode step
at published widths. Nothing runs; the TPU compiler refuses here what the
chip would refuse (misaligned blocks, VMEM overflow, HBM overflow), so
these tests guard the chip path without a chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and a worker that cannot describe the topology skips these tests instead
of collecting a different test set."""

import dataclasses
import functools
import os
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

V5E_HBM_BYTES = 16 * 2**30
N_STREAM = 1 << 27        # 512 MiB of f32: an HBM-sized streaming input


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off: an entry written for a chip that is not attached cannot
    be read back here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# name -> (wrapper with interpret=False, ((shape, dtype), ...))
KERNELS = {
    "va": (functools.partial(ops.va, interpret=False),
           (((N_STREAM,), jnp.float32), ((N_STREAM,), jnp.float32))),
    "reduction": (functools.partial(ops.reduction, interpret=False),
                  (((N_STREAM,), jnp.float32),)),
    "scan": (functools.partial(ops.scan, interpret=False),
             (((N_STREAM,), jnp.float32),)),
    "gemv": (functools.partial(ops.gemv, interpret=False),
             (((12800, 4096), jnp.bfloat16), ((4096,), jnp.bfloat16))),
    "histogram": (functools.partial(ops.histogram, bins=256,
                                    interpret=False),
                  (((1 << 24,), jnp.uint32),)),
    "ts_min": (functools.partial(ops.ts_min, interpret=False),
               (((1 << 20,), jnp.int32), ((64,), jnp.int32))),
    "transpose": (functools.partial(ops.transpose, interpret=False),
                  (((4096, 4096), jnp.float32),)),
    "decode_attention": (
        functools.partial(ops.decode_attention, interpret=False),
        (((8, 32, 128), jnp.bfloat16), ((8, 2048, 8, 128), jnp.bfloat16),
         ((8, 2048, 8, 128), jnp.bfloat16), ((), jnp.int32))),
    "stream_ops": (functools.partial(ops.stream_ops, ops_per_elem=4,
                                     interpret=False),
                   (((N_STREAM,), jnp.int32),)),
    "flash_attention": (
        functools.partial(ops.flash_attention, causal=True,
                          interpret=False),
        (((1, 2048, 32, 128), jnp.bfloat16),
         ((1, 2048, 8, 128), jnp.bfloat16),
         ((1, 2048, 8, 128), jnp.bfloat16))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel"


@pytest.fixture(scope="module")
def granite_20l(one_chip):
    """The engine's fused steps for granite-3-8b at published widths, cut
    to 20 of 40 layers, 8 slots x 2048 positions, compiled for one v5e:
    (compiled(program), params), `program` "decode" or "prefill" (one
    256-token prompt into one slot)."""
    from repro.configs.granite_3_8b import CONFIG
    from repro.models import Shardings, init_cache
    from repro.models.transformer import param_shape_structs
    from repro.serve import ServeEngine

    cfg = dataclasses.replace(CONFIG, n_layers=20)
    slots, max_len = 8, 2048
    on_chip = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        t)
    params = on_chip(param_shape_structs(cfg))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, slots, max_len)))
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    # the engine's own steps, bound to a stand-in so no cache is allocated
    engine = types.SimpleNamespace(cfg=cfg, shd=Shardings(None),
                                   temperature=0.0, max_len=max_len)

    @functools.cache
    def compiled(program):
        if program == "decode":
            step = functools.partial(ServeEngine._decode_step_fn, engine)
            args = (arg((slots, 1), jnp.int32), arg((slots,), jnp.int32),
                    arg((slots,), jnp.bool_), arg((2,), jnp.uint32))
        else:
            step = functools.partial(ServeEngine._prefill_one_fn, engine)
            args = (arg((256,), jnp.int32), arg((), jnp.int32))
        return jax.jit(step).lower(params, cache, *args).compile()

    return compiled, params


def test_granite_decode_step_fits_one_v5e(granite_20l):
    """The decode step compiles for one v5e and fits its HBM (weights, KV
    cache, the new cache and temporaries)."""
    compiled, params = granite_20l
    mem = compiled("decode").memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    weights = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(params))
    assert weights > 8e9, weights              # published widths, 20 layers
    assert total < V5E_HBM_BYTES, (total, mem)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_cached_steps_copy_no_weights(granite_20l, program):
    """A cached step scans one layer at a time, so each layer's weight
    slice fuses into its matmul: the temporaries stay below one layer's
    weights (a scan over groups of `remat_group` layers copied each
    group out of the stack, about 2.9 GB here)."""
    compiled, params = granite_20l
    layers = params["layers"]
    n_blocks = jax.tree.leaves(layers)[0].shape[0]
    layer_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(layers)) // n_blocks
    assert layer_bytes > 3.9e8, layer_bytes
    mem = compiled(program).memory_analysis()
    assert mem.temp_size_in_bytes < layer_bytes, (layer_bytes, mem)
