"""The served programs of granite-moe-1b-a400m, compiled at its published
widths for one TPU v5e chip.

The TPU compiler runs here on a described topology, with no chip
attached: a program the chip's compiler refuses, or one that does not
fit the chip's memory, fails here.  Nothing runs, so these tests say
nothing about results or times.
"""

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.serving import ServingEngine

ARCH = "granite-moe-1b-a400m"
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("entry,program", [
    ("generate", "prefill"), ("generate", "decode"), ("score", "score")])
def test_served_program_fits_one_v5e(one_chip, entry, program):
    eng = ServingEngine(get_config(ARCH))
    fn, args = eng.entry_programs(entry)[program]
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        args)
    mem = fn.lower(*args).compile().memory_analysis()
    param_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(args[0]))
    assert param_bytes > 2 * 2**30  # published widths, not a reduced config
    assert mem.argument_size_in_bytes >= param_bytes
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, (program, used)
