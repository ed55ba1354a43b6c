"""Every serving Pallas kernel compiles for a TPU v5e.

The kernel tests elsewhere run under ``interpret=True``, which never asks
the TPU compiler anything: unsupported primitives, unaligned blocks and
VMEM overflows only show here.  Each test lowers and compiles one kernel
for a *described* ``v5e:2x2`` chip (``jax.experimental.topologies``; no
chip needs to be attached) at the shapes ``chip_smoke.py`` serves, and
checks that the compiled HLO holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.metropolis_sweep import metropolis_sweep_pallas
from repro.kernels.qap_sweep import qap_sweep_pallas
from repro.kernels.reduce_min import block_argmin_pallas

#: The smoke's kernel block: chains_per_slot=256 (EngineConfig in
#: chip_smoke.py); two blocks make the grid real without adding compile time.
BLK = 256
N_BLOCKS = 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


#: Widths of the paper's Table 8 suite as the server runs it
#: (``bench/configs/suite-table8-fleet4.json``): each width that is not a
#: power of two, and 4, 8 and 512.
SUITE_DIMS = [4, 8, 10, 30, 100, 200, 400, 512]


@pytest.mark.parametrize("dim", SUITE_DIMS)
@pytest.mark.parametrize("variant", ["delta", "full"])
def test_metropolis_sweep_compiles(one_chip, variant, dim):
    """The engine's form: runtime objective id, level cursor and per-chain
    temperatures, as ``_group_tick_fused`` calls it."""
    def sweep(x, kid, T, seed, step0, base, live, t_chain):
        return metropolis_sweep_pallas(
            x, T, seed, step0, kid=kid, n_steps=100, blk=BLK,
            variant=variant, chain_base=base, live=live, t_chain=t_chain)

    nb, chains = N_BLOCKS, N_BLOCKS * BLK
    text = _compile_text(
        sweep, one_chip, ((chains, dim), jnp.float32), ((nb,), jnp.int32),
        ((nb,), jnp.float32), ((nb,), jnp.uint32), ((nb,), jnp.uint32),
        ((nb,), jnp.uint32), ((nb,), jnp.int32), ((chains,), jnp.float32))
    assert "tpu_custom_call" in text


def test_qap_sweep_compiles(one_chip):
    """n=12 (the largest built-in instance), packed per-block F/D."""
    n, nb = 12, N_BLOCKS

    def sweep(p, F, D, T, seed, step0, base, live):
        return qap_sweep_pallas(p, F, D, T, seed, step0, n_steps=25, blk=BLK,
                                chain_base=base, live=live)

    text = _compile_text(
        sweep, one_chip, ((nb * BLK, n), jnp.int32),
        ((nb * n, n), jnp.float32), ((nb * n, n), jnp.float32),
        ((nb,), jnp.float32), ((nb,), jnp.uint32), ((nb,), jnp.uint32),
        ((nb,), jnp.uint32), ((nb,), jnp.int32))
    assert "tpu_custom_call" in text


def test_block_argmin_compiles(one_chip):
    text = _compile_text(lambda f: block_argmin_pallas(f, blk=1024),
                         one_chip, ((16384,), jnp.float32))
    assert "tpu_custom_call" in text
