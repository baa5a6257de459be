"""Device-plane PGAS acceptance example (round 4).

The OpenSHMEM circular-shift example (the reference's
examples/oshmem_circular_shift.c shape) executed on the DEVICE plane:
the symmetric heap lives in HBM as jax Arrays sharded one-shard-per-PE
over an 8-device mesh, and every put/get/fetch-add is part of a
compiled epoch (ppermute + dynamic-update schedules —
zhpe_ompi_tpu/shmem/device.py, the spml/ucx fast-fabric inversion).

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python examples/device_pgas.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp

    import zhpe_ompi_tpu as zmpi
    from zhpe_ompi_tpu.shmem import spml

    world = zmpi.init()
    n = world.axis_size

    # shmem_init on a device communicator selects the "device" spml
    heap = spml.shmem_pe(world, heap_bytes=1 << 14)
    assert heap.plane == "device", heap
    src = heap.shmalloc(4, np.float32)
    counter = heap.shmalloc(1, np.float32)

    def epoch(pe, _):
        me = pe.my_pe().astype(jnp.float32)
        pe = pe.local_set(src, me)
        pe = pe.local_set(counter, 0.0)
        pe = pe.barrier()
        # circular shift: put my block into my right neighbor's heap
        pe = pe.put(src, jnp.full(4, me), pe_of=lambda r, k: (r + 1) % k)
        # and bump their visit counter (one writer per target per epoch)
        old, pe = pe.fadd(counter, 1.0, pe_of=lambda r, k: (r + 1) % k)
        # read back what my LEFT neighbor now holds (two hops of data)
        got = pe.get(src, pe_of=lambda r, k: (r - 1) % k)
        return pe, got[None]

    out = np.asarray(heap.epoch(epoch, jnp.zeros((n, 1))))
    shifted = heap.read(src)
    counts = heap.read(counter)

    for r in range(n):
        assert np.allclose(shifted[r], (r - 1) % n), shifted[r]
        assert counts[r] == 1.0, counts[r]
        # PE r read PE r-1's post-shift block, which holds r-2's rank
        assert np.allclose(out[r], (r - 2) % n), out[r]
    heap.finalize()
    print(f"device_pgas: {n} PEs, HBM symmetric heap, compiled "
          f"put/fadd/get epochs — PASSED")


if __name__ == "__main__":
    main()
