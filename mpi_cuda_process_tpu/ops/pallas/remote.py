"""In-kernel remote-DMA ring exchange: device-initiated halo transfers.

Every exchange before this module was an XLA-level ``jax.lax.ppermute``
on materialized HBM slabs — even the pipelined schedule still staged
each slab through HBM between passes.  This module issues the neighbor
transfer *inside* a Pallas kernel instead: each device's boundary slab
is staged chunk-by-chunk through a double-buffered VMEM ring and pushed
straight into the neighbor's incoming VMEM ring with
``pltpu.make_async_remote_copy`` under send/recv DMA semaphores — the
device-initiated-communication discipline that lets the TPU distributed
linear-algebra work (arXiv:2112.09017) and the TPU CFD framework
(arXiv:2108.11076) scale stencil-shaped traffic to thousands of cores
without host- or HBM-staged halos.  Exchange latency becomes a
per-chunk, not per-slab, quantity: chunk ``i+1``'s send overlaps chunk
``i``'s drain on the receiving side.

Protocol of one :func:`build_ring_exchange_call` invocation (both ring
directions of ONE mesh axis, one field):

  1. **barrier** (``pltpu.get_barrier_semaphore``, per-call
     ``collective_id``): signal both ring neighbors, wait for both —
     no remote write ever lands in a VMEM ring that is not yet alive
     (neighbor-readiness, and the cross-invocation fence that keeps a
     scan body's iteration ``i+1`` sends out of iteration ``i``'s
     buffers).
  2. per chunk ``c`` and direction ``d`` (down = toward the next shard,
     up = toward the previous): local async-copy the chunk into send
     slot ``c % 2``, then ``make_async_remote_copy`` send-slot ->
     neighbor's recv slot ``c % 2`` (REGULAR send/recv DMA semaphores;
     the symmetric SPMD op means *my* recv semaphore is signaled by my
     opposite neighbor's send of the same chunk).
  3. drain: wait recv, local async-copy recv slot -> the output slab's
     chunk, then **credit** the sender (a remote ``semaphore_signal``
     on a per-direction REGULAR semaphore) so it may reuse that recv
     slot.  A sender consumes one credit before issuing chunk ``c >= 2``
     — two slots, two in-flight chunks, classic capacity-2 flow
     control.  Double buffering is exactly why chunk ``i+1``'s send
     overlaps chunk ``i``'s compute on both ends.
  4. epilogue: wait the trailing sends and consume the trailing
     credits, so every semaphore is provably zero at kernel exit (the
     Mosaic drained-semaphore invariant).

The ring is ALWAYS full (every device sends in both directions, mod the
ring) — uniform SPMD, no per-rank branching, no device ever blocks on a
transfer its neighbor never issues; non-periodic walls substitute the
guard-cell constant on the *received* slab outside the kernel
(``parallel/halo.py``), exactly like the truncated-``ppermute`` path.

**Interpret-mode execution path** (tier-1 CPU proof): JAX 0.4.x's
interpret-mode discharge of a *remote* ``dma_start`` only supports
single-named-axis meshes (``dma_start_discharge_rule``), and this
package's meshes always carry three named axes — so ``remote=False``
builds the same kernel in **loopback** mode: the identical chunked,
double-buffered VMEM-ring machinery runs end-to-end in interpret mode,
with the cross-chip hop replaced by a local copy into a "wire" output
that the caller ring-shifts at the JAX level (``lax.all_gather`` + a
dynamic index — zero ``ppermute``, the same emulation the upstream
discharge rule performs where it applies).  The caller records which
path ran (``RdmaTransport.backend``) so telemetry carries an honest
mode tag instead of a silent skip.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compat import compiler_params
from .fused import _sublane
from .kernels import _VMEM_LIMIT_BYTES

# Ring slots per direction: 2 = the minimum that lets chunk i+1's send
# overlap chunk i's drain (capacity-2 credit flow control).  The ISSUE's
# "double-buffered recv slots".  This is the DEFAULT only: the kernel
# variant autotuner (policy/autotune.py) sweeps deeper rings through the
# ``nslots=`` parameters below, and the credit capacity scales with it.
_NSLOTS = 2


def _nc_ladder(nslots: int) -> Tuple[int, int]:
    """Chunk-count ladder, largest first: more chunks = finer
    send/compute overlap, but every chunk pays a semaphore round-trip.
    The floor is the slot count itself — fewer chunks than slots would
    leave ring capacity idle — so the ladder scales with the ring depth
    instead of hardcoding the historical 2-slot ``(4, 2)``."""
    return (2 * nslots, nslots)


def pick_chunks(shape: Tuple[int, ...], itemsize: int,
                nslots: int = _NSLOTS,
                prefer_nc: int = 0) -> Tuple[int, int]:
    """``(chunk_axis, nchunks)`` for a slab of ``shape``.

    The single source of chunk geometry — the kernel builder AND the
    analytic cost model (``obs/costmodel.py``) both call this, so the
    manifest's rdma round counters cross-check against the kernel's
    actual DMA grid by construction.  Axis 2 (lanes) is never chunked;
    axis 1 is the sublane axis, so its chunk extent must stay
    tile-aligned (the same DMA-offset discipline as streamfused's
    ``wm_a``); axis 0 offsets are free.  Prefers the sublane axis when
    both qualify (tile-shaped chunks), falls back to a single chunk
    when nothing divides.

    ``nslots`` scales the ladder floor (a deeper ring wants at least as
    many chunks as slots); ``prefer_nc`` prepends a variant-requested
    chunk count that still must pass the same divisibility/alignment
    gates — an autotuner candidate can steer the geometry but never
    bypass the constraints.  The defaults reproduce the historical
    ``(4, 2)`` ladder byte-for-byte.
    """
    sub = _sublane(itemsize)
    ladder = ((int(prefer_nc),) if prefer_nc else ()) \
        + _nc_ladder(int(nslots))
    for nc in ladder:
        for axis in (1, 0):
            ext = int(shape[axis])
            if ext % nc:
                continue
            if axis == 1 and (ext // nc) % sub:
                continue
            return axis, nc
    return 0, 1


def _chunk_at(ref, axis: int, start, size: int):
    idx = [slice(None)] * 3
    idx[axis] = pl.ds(start, size)
    return ref.at[tuple(idx)]


def _ring_kernel(nc, axis, csize, nslots, remote, *refs):
    """Both ring directions of one slab pair through the VMEM rings.

    ``refs`` = ``[nbr_ids (SMEM int32 (2,))] +`` (remote only) ``[hi,
    lo]`` HBM inputs ``+ [from_left/wire_hi, from_right/wire_lo]`` HBM
    outputs.  Direction 0 sends ``hi`` down-ring (lands as the next
    shard's ``from_left``), direction 1 sends ``lo`` up-ring.

    ``nslots`` is the ring depth per direction (default 2): the
    in-flight window, the credit capacity, and the scratch/semaphore
    shapes all derive from it, so the drained-semaphore arithmetic
    below holds for ANY depth — credits signaled per direction = nc,
    consumed = max(0, nc - nslots) in the flow-control window plus
    min(nslots, nc) in the epilogue = nc; sends waited = nc.
    """
    if remote:
        nbr, refs = refs[0], refs[1:]
    ins = refs[:2]
    outs = refs[2:4]

    def body(send_buf, recv_buf, load_sems, drain_sems, send_sems,
             recv_sems, credit=None):
        def load(d, c):
            return pltpu.make_async_copy(
                _chunk_at(ins[d], axis, c * csize, csize),
                send_buf.at[d, c % nslots],
                load_sems.at[d, c % nslots])

        def xfer(d, c):
            slot = c % nslots
            if remote:
                return pltpu.make_async_remote_copy(
                    src_ref=send_buf.at[d, slot],
                    dst_ref=recv_buf.at[d, slot],
                    send_sem=send_sems.at[d, slot],
                    recv_sem=recv_sems.at[d, slot],
                    device_id=nbr[d],
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
            # loopback: same slot discipline, local hop into OWN ring
            return pltpu.make_async_copy(
                send_buf.at[d, slot], recv_buf.at[d, slot],
                recv_sems.at[d, slot])

        def drain(d, c):
            return pltpu.make_async_copy(
                recv_buf.at[d, c % nslots],
                _chunk_at(outs[d], axis, c * csize, csize),
                drain_sems.at[d, c % nslots])

        if remote:
            # Neighbor-readiness barrier: no remote write may land in a
            # VMEM ring that is not yet (or no longer) alive.
            bar = pltpu.get_barrier_semaphore()
            for d in (0, 1):
                pltpu.semaphore_signal(
                    bar, 1, device_id=nbr[d],
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
            pltpu.semaphore_wait(bar, 2)
        # prologue: fill the ring per direction (no credit needed —
        # all remote recv slots start free)
        for c in range(min(nslots, nc)):
            for d in (0, 1):
                load(d, c).start()
                load(d, c).wait()
                xfer(d, c).start()
        for c in range(nc):
            for d in (0, 1):
                if remote:
                    xfer(d, c).wait_recv()  # my chunk c has landed
                else:
                    xfer(d, c).wait()
                drain(d, c).start()
                drain(d, c).wait()
                if remote:
                    # slot freed: credit the device that sends INTO this
                    # direction's ring (my opposite-direction neighbor)
                    pltpu.semaphore_signal(
                        credit.at[d], 1, device_id=nbr[1 - d],
                        device_id_type=pltpu.DeviceIdType.LOGICAL)
            if c + nslots < nc:
                for d in (0, 1):
                    if remote:
                        # capacity-nslots flow control: reuse the remote
                        # recv slot only after its drain was credited,
                        # and the send slot only after its send left
                        pltpu.semaphore_wait(credit.at[d], 1)
                        xfer(d, c).wait_send()
                    load(d, c + nslots).start()
                    load(d, c + nslots).wait()
                    xfer(d, c + nslots).start()
        if remote:
            # epilogue: every semaphore must read zero at kernel exit
            for c in range(max(0, nc - nslots), nc):
                for d in (0, 1):
                    xfer(d, c).wait_send()
            for d in (0, 1):
                pltpu.semaphore_wait(credit.at[d], min(nslots, nc))

    cshape = list(ins[0].shape)
    cshape[axis] = csize
    kwargs = dict(
        send_buf=pltpu.VMEM((2, nslots, *cshape), ins[0].dtype),
        recv_buf=pltpu.VMEM((2, nslots, *cshape), ins[0].dtype),
        load_sems=pltpu.SemaphoreType.DMA((2, nslots)),
        drain_sems=pltpu.SemaphoreType.DMA((2, nslots)),
        send_sems=pltpu.SemaphoreType.DMA((2, nslots)),
        recv_sems=pltpu.SemaphoreType.DMA((2, nslots)),
    )
    if remote:
        kwargs["credit"] = pltpu.SemaphoreType.REGULAR((2,))
    pl.run_scoped(functools.partial(body), **kwargs)


def build_ring_exchange_call(
    shape: Tuple[int, ...],
    dtype,
    *,
    remote: bool,
    interpret: bool,
    collective_id: int = 0,
    chunks: Optional[Tuple[int, int]] = None,
    nslots: Optional[int] = None,
    prefer_nc: int = 0,
):
    """One ring-exchange ``pallas_call`` for slabs of ``shape``/``dtype``.

    ``remote=True`` (compiled TPU path): ``call(nbr_ids, hi, lo) ->
    (from_left, from_right)`` where ``nbr_ids`` is an int32 ``(2,)``
    SMEM operand holding the [down, up] LOGICAL neighbor device ids
    (``parallel/halo.neighbor_logical_ids``) and the outputs are what
    the two ring neighbors pushed into this device's recv rings.

    ``remote=False`` (loopback, the interpret-mode execution path):
    ``call(hi, lo) -> (wire_hi, wire_lo)`` — the identical chunked
    double-buffered ring machinery with the cross-chip hop removed;
    the caller ring-shifts the wire outputs at the JAX level.

    Returns ``(call, meta)``; ``meta`` records the chunk geometry the
    cost model cross-checks (axis, nchunks, chunk/slab bytes, slots).
    ``nslots``/``prefer_nc`` are the kernel-variant knobs (ring depth
    and chunk-count preference, policy/autotune.py); the defaults are
    the historical 2-slot geometry.
    """
    shape = tuple(int(s) for s in shape)
    assert len(shape) == 3, shape
    itemsize = jnp.dtype(dtype).itemsize
    nslots = int(nslots) if nslots else _NSLOTS
    if chunks is None:
        chunks = pick_chunks(shape, itemsize, nslots=nslots,
                             prefer_nc=prefer_nc)
    axis, nc = chunks
    assert shape[axis] % nc == 0, (shape, chunks)
    csize = shape[axis] // nc

    kernel = functools.partial(_ring_kernel, nc, axis, csize, nslots,
                               remote)
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
    if remote:
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
    cp = None
    if not interpret:
        cp = compiler_params(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
            **({"collective_id": int(collective_id)} if remote else {}))
    call = pl.pallas_call(
        kernel,
        name="halo_ring_dma",
        in_specs=in_specs,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_shape=[jax.ShapeDtypeStruct(shape, dtype)] * 2,
        interpret=interpret,
        compiler_params=cp,
    )
    meta = {
        "shape": shape,
        "dtype": str(jnp.dtype(dtype)),
        "chunk_axis": axis,
        "nchunks": nc,
        "nslots": nslots,
    }
    meta["slab_bytes"] = shape[0] * shape[1] * shape[2] * itemsize
    meta["chunk_bytes"] = meta["slab_bytes"] // nc
    # one call moves BOTH directions: 2*nc remote DMAs, 2 slabs of bytes
    meta["remote_dma_per_call"] = 2 * nc
    meta["ici_bytes_per_call"] = 2 * meta["slab_bytes"]
    return call, meta


def ring_exchange_stats(shape: Tuple[int, ...], dtype,
                        nslots: Optional[int] = None,
                        prefer_nc: int = 0) -> dict:
    """Chunk geometry + per-call DMA/byte counts WITHOUT building the
    kernel — the analytic half of the costmodel cross-check, guaranteed
    consistent with the kernel because both read :func:`pick_chunks`
    (same ``nslots``/``prefer_nc`` variant knobs as the builder)."""
    shape = tuple(int(s) for s in shape)
    itemsize = jnp.dtype(dtype).itemsize
    nslots = int(nslots) if nslots else _NSLOTS
    axis, nc = pick_chunks(shape, itemsize, nslots=nslots,
                           prefer_nc=prefer_nc)
    slab_bytes = shape[0] * shape[1] * shape[2] * itemsize
    return {
        "shape": list(shape),
        "chunk_axis": axis,
        "nchunks": nc,
        "nslots": nslots,
        "chunk_bytes": slab_bytes // nc,
        "remote_dma_per_call": 2 * nc,
        "ici_bytes_per_call": 2 * slab_bytes,
    }
