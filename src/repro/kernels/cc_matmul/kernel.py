"""Pallas collective-matmul kernels: the ring hop consumed *inside* the
kernel (SMI-style), instead of alternating ``ppermute`` with whole XLA
sub-matmul calls like ``core/overlap.py``.

Two paths, one schedule:

* **Remote-DMA path** (:func:`ag_matmul_ring_tpu`,
  :func:`rs_matmul_ring_tpu`) — a single ``pallas_call`` per collective
  matmul.  Hop *k+1*'s chunk is launched with
  ``pltpu.make_async_remote_copy`` into the free slot of a double-buffered
  VMEM scratch while hop *k*'s tile multiplies on the MXU; send/recv DMA
  semaphores fence slot reuse.  No XLA launch or HBM repack boundary
  between hops — the FPGA-native overlap of the Streaming Message
  Interface, played by the TPU DMA engines.  Requires a TPU backend
  (``kernels.common.supports_remote_dma``); there is no interpreter
  emulation of remote DMA.
* **Emulated path** (:func:`consume_matmul`, :func:`consume_matmul_acc`,
  :func:`matmul_tile`) — the hop itself stays a ``lax.ppermute`` (driven
  by ``ops.py``), but every arrival lands in the same double-buffered
  scratch layout and is consumed by a Pallas kernel reading its slot, so
  CPU CI exercises the identical code structure.  Under the interpreter
  the consume kernel lowers to the same ``jnp.dot`` the reference schedule
  issues, so the emulated path is **bit-identical** to ``core/overlap.py``
  (asserted in ``tests/test_overlap.py``).

Both paths run inside ``shard_map`` over a 1-D ring axis.  The per-hop
schedules mirror ``core/overlap.py`` op-for-op:

* all-gather matmul, hop *k* (direction *d*): multiply the block of rank
  ``(my − d·k) % n`` that just landed, place it at row
  ``src · b_stride + row_off`` of the output, while block *k+1* is in
  flight.
* matmul reduce-scatter, hop *k*: the accumulator rides the ring; after it
  lands, add the local partial ``dot(row_block(−d·(k+1)), w)`` computed
  under its flight, and forward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _compiler_params(collective_id: int):
    return pltpu.CompilerParams(has_side_effects=True,
                                collective_id=collective_id)


# ---------------------------------------------------------------------------
# Emulated path: per-hop consume kernels over the double-buffered scratch
# ---------------------------------------------------------------------------


def _matmul_tile_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32)


def matmul_tile(x: jnp.ndarray, w: jnp.ndarray, *,
                interpret: bool) -> jnp.ndarray:
    """The resident block's tile: ``dot(x, w)`` in f32 (hop 0 has no
    arrival to consume, but still runs through the kernel surface)."""
    return pl.pallas_call(
        _matmul_tile_kernel,
        out_shape=jax.ShapeDtypeStruct((x.shape[0], w.shape[1]),
                                       jnp.float32),
        interpret=interpret,
    )(x, w)


def _consume_kernel(scr_ref, w_ref, o_ref, *, slot: int):
    # the hop's chunk is read straight out of its scratch slot — the
    # in-kernel message consumption the remote-DMA path does for real
    o_ref[...] = jnp.dot(scr_ref[slot], w_ref[...],
                         preferred_element_type=jnp.float32)


def consume_matmul(scratch: jnp.ndarray, w: jnp.ndarray, *, slot: int,
                   interpret: bool) -> jnp.ndarray:
    """AG hop consume: ``dot(scratch[slot], w)`` → (b, N) f32.

    ``scratch``: (2, b, K) double buffer; ``slot`` is static (the ring
    loop is python-unrolled, hop *k* lands in slot ``k % 2``).
    """
    return pl.pallas_call(
        functools.partial(_consume_kernel, slot=slot),
        out_shape=jax.ShapeDtypeStruct((scratch.shape[1], w.shape[1]),
                                       jnp.float32),
        interpret=interpret,
    )(scratch, w)


def _consume_acc_kernel(scr_ref, x_ref, w_ref, o_ref, *, slot: int):
    # arrived accumulator + the local partial computed under its flight —
    # same add order as core/overlap.py (arr + dot), so bit-identical
    o_ref[...] = scr_ref[slot] + jnp.dot(x_ref[...], w_ref[...],
                                         preferred_element_type=jnp.float32)


def consume_matmul_acc(scratch: jnp.ndarray, x: jnp.ndarray,
                       w: jnp.ndarray, *, slot: int,
                       interpret: bool) -> jnp.ndarray:
    """RS hop consume: ``scratch[slot] + dot(x, w)`` → (b, N) f32.

    ``scratch``: (2, b, N) f32 double buffer of in-flight accumulators.
    """
    return pl.pallas_call(
        functools.partial(_consume_acc_kernel, slot=slot),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], w.shape[1]),
                                       jnp.float32),
        interpret=interpret,
    )(scratch, x, w)


# ---------------------------------------------------------------------------
# Remote-DMA path: the whole ring inside one pallas_call (TPU only)
# ---------------------------------------------------------------------------


def _peer(axis: str, rank):
    """Remote-DMA address of ``rank`` on ring ``axis``: mesh coordinates,
    with every other mesh axis held at this device's own index."""
    return dict(device_id={axis: rank},
                device_id_type=pltpu.DeviceIdType.MESH)


def _neighbor_barrier(axis: str, n: int):
    """Rendezvous with both ring neighbors before touching their VMEM —
    the standard guard against a fast rank DMA-ing into a peer whose
    previous kernel still owns the comm buffer."""
    my = lax.axis_index(axis)
    barrier = pltpu.get_barrier_semaphore()
    for nb in (1, n - 1):
        pltpu.semaphore_signal(barrier, inc=1, **_peer(axis, (my + nb) % n))
    pltpu.semaphore_wait(barrier, 2)


def _slot_free(ready_sem, axis: str, n: int, direction: int):
    """Tell the upstream rank (the one that DMAs into this rank) that the
    slot it writes next is no longer read here.  Paired one-for-one with
    a ``semaphore_wait(ready_sem, 1)`` before each send into a slot the
    downstream rank has used, so a fast rank never overwrites a block its
    neighbor is still multiplying or forwarding."""
    my = lax.axis_index(axis)
    pltpu.semaphore_signal(ready_sem, inc=1,
                           **_peer(axis, (my - direction) % n))


def _ag_ring_kernel(x_ref, w_ref, o_ref, comm_ref, local_sem, send_sem,
                    recv_sem, ready_sem, *, axis: str, n: int,
                    direction: int):
    my = lax.axis_index(axis)
    b = x_ref.shape[0]
    _neighbor_barrier(axis, n)

    # seed slot 0 with the resident block
    seed = pltpu.make_async_copy(x_ref, comm_ref.at[0], local_sem)
    seed.start()
    seed.wait()

    def rdma(hop):
        # forward the block in hand to the next rank's free slot
        return pltpu.make_async_remote_copy(
            src_ref=comm_ref.at[hop % 2],
            dst_ref=comm_ref.at[(hop + 1) % 2],
            send_sem=send_sem, recv_sem=recv_sem,
            **_peer(axis, (my + direction) % n))

    for hop in range(n):
        if hop + 1 < n:
            if hop >= 1:
                # the downstream slot held its hop-(hop-1) block
                pltpu.semaphore_wait(ready_sem, 1)
            rdma(hop).start()               # hop k+1's chunk in flight ...
        src = (my - direction * hop) % n
        o_ref[pl.ds(src * b, b), :] = jnp.dot(
            comm_ref[hop % 2], w_ref[...],
            preferred_element_type=jnp.float32)  # ... while hop k multiplies
        if hop + 1 < n:
            rdma(hop).wait()                # fence both slots before reuse
            if hop + 2 < n:
                _slot_free(ready_sem, axis, n, direction)


def ag_matmul_ring_tpu(x: jnp.ndarray, w: jnp.ndarray, *, axis: str,
                       n: int, direction: int = 1, collective_id: int = 0):
    """One-direction in-kernel AG matmul: (b, K) @ (K, N) → (n·b, N) f32,
    blocks in axis-index order.  The bidirectional composition in
    ``ops.py`` runs this twice (counter-rotating halves, distinct
    ``collective_id``) and interleaves the compact outputs."""
    b = x.shape[0]
    kernel = functools.partial(
        _ag_ring_kernel, axis=axis, n=n, direction=direction)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n * b, w.shape[1]), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, b, x.shape[1]), x.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=_compiler_params(collective_id),
    )(x, w)


def _rs_ring_kernel(x_ref, w_ref, o_ref, comm_ref, send_sem, recv_sem,
                    ready_sem, *, axis: str, n: int, direction: int,
                    b_loc: int):
    my = lax.axis_index(axis)

    def partial_block(hop):
        # the block that must travel farthest next (overlap.py row_block)
        off = -direction * (hop + 1)
        start = ((my + off) % n) * b_loc
        return jnp.dot(x_ref[pl.ds(start, b_loc), :], w_ref[...],
                       preferred_element_type=jnp.float32)

    _neighbor_barrier(axis, n)
    comm_ref[0] = partial_block(0)

    def rdma(hop):
        return pltpu.make_async_remote_copy(
            src_ref=comm_ref.at[(hop - 1) % 2],
            dst_ref=comm_ref.at[hop % 2],
            send_sem=send_sem, recv_sem=recv_sem,
            **_peer(axis, (my + direction) % n))

    for hop in range(1, n):
        if hop >= 2:
            # the downstream slot still held its hop-(hop-1) send source
            pltpu.semaphore_wait(ready_sem, 1)
        rdma(hop).start()                   # accumulator rides the ring ...
        part = partial_block(hop)           # ... under the local partial
        rdma(hop).wait()
        comm_ref[hop % 2] = comm_ref[hop % 2] + part
        if hop + 1 < n:
            _slot_free(ready_sem, axis, n, direction)

    o_ref[...] = comm_ref[(n - 1) % 2]


def rs_matmul_ring_tpu(x: jnp.ndarray, w: jnp.ndarray, *, axis: str,
                       n: int, direction: int = 1,
                       collective_id: int = 0):
    """One-direction in-kernel matmul RS: (n·b, K) @ (K, N) → (b, N) f32."""
    b_loc = x.shape[0] // n
    kernel = functools.partial(
        _rs_ring_kernel, axis=axis, n=n, direction=direction, b_loc=b_loc)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b_loc, w.shape[1]), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, b_loc, w.shape[1]), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=_compiler_params(collective_id),
    )(x, w)


__all__ = [
    "matmul_tile", "consume_matmul", "consume_matmul_acc",
    "ag_matmul_ring_tpu", "rs_matmul_ring_tpu",
]
