"""The main path compiled for a described TPU v5e (on-chip-measurement §2).

The TPU compiler is installed here and compiles for a chip that is
described, not attached: what the chip's compiler refuses (a block not
aligned to the tiling, too much VMEM, a program that does not fit HBM)
fails here at no chip time.  Nothing runs, so these say nothing about
results or times; ``chip_smoke.py`` on the chip does.

The topology is described in a module fixture, never at import: only one
process may load libtpu, and the test workers import every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

HBM_BYTES = 16 << 30  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    # The TPU compiler's threads start with libtpu and inherit this
    # thread's CPU mask: keep them on one core, so the compiles do not
    # starve the other test workers' timing-sensitive heartbeats.
    cpus = os.sched_getaffinity(0)
    os.sched_setaffinity(0, {max(cpus)})
    cache_on = jax.config.jax_enable_compilation_cache
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 - no libtpu here
                pytest.skip(
                    f"no v5e:2x2 topology can be described here: {e}")
            # a described compile cannot be read back from the
            # persistent cache without a chip: keep it out
            jax.config.update("jax_enable_compilation_cache", False)
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        os.sched_setaffinity(0, cpus)


def _compile_for_chip(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "no Pallas kernel in the compiled program"
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.1f} GiB does not fit"
    return compiled


def _grad_of(fn, n_args):
    return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                    argnums=tuple(range(n_args)))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", [(16, 512, 16, 64), (2, 4096, 16, 64)],
                         ids=["seq512", "seq4096"])
def test_flash_compiles(one_chip, shape, direction):
    from zhpe_ompi_tpu.ops.flash_attention import _flash

    S = shape[1]
    block_q, block_k = min(512, S), min(1024, S)  # flash_attention's

    def fwd(q, k, v):
        return _flash(q, k, v, True, block_q, block_k, False)

    fn = fwd if direction == "fwd" else _grad_of(fwd, 3)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    _compile_for_chip(jax.jit(fn).lower(q, q, q))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_layernorm_compiles(one_chip, direction):
    from zhpe_ompi_tpu.ops.fused_norm import _ln_pallas

    def fwd(x, g):
        return _ln_pallas(x, g, 256, False)

    fn = fwd if direction == "fwd" else _grad_of(fwd, 2)
    x = jax.ShapeDtypeStruct((8192, 1024), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((1024,), jnp.float32, sharding=one_chip)
    _compile_for_chip(jax.jit(fn).lower(x, g))


def test_train_step_compiles(one_chip, monkeypatch):
    """``make_train_step`` at the chip width, with the dispatchers
    steered to their TPU branch (they ask ``jax.devices()``, which here
    is the CPU)."""
    import bench
    import zhpe_ompi_tpu as zmpi
    from zhpe_ompi_tpu.models import transformer as tfm
    from zhpe_ompi_tpu.ops import flash_attention, fused_norm

    monkeypatch.setattr(flash_attention, "on_tpu", lambda: True)
    monkeypatch.setattr(fused_norm, "on_tpu", lambda: True)
    cfg = bench.chip_config(512)
    mesh = bench.dp_tp_mesh(list(one_chip.device_set))
    dp_comm = zmpi.Communicator(mesh, "dp", name="aot_dp")
    step, specs = tfm.make_train_step(cfg, mesh, dp_comm, None)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    params = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                sharding=NamedSharding(mesh, specs[k]))
        for k, v in shapes.items()
    }
    tok = jax.ShapeDtypeStruct((16, cfg.seq), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp")))
    _compile_for_chip(step.lower(params, tok, tok))
