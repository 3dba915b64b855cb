"""Measure HBM->VMEM tile-streaming cost for a node-blocked scan step.

The fused scan kernel keeps all persistent node-state tiles resident
in VMEM; past the ~13 MB budget the plan rejects (see tools/
vmem_map.py for where that lands per scenario flavor). The candidate
mitigation is node-axis blocking: state lives in HBM and every pod
step streams it through VMEM in (B, 128) blocks. Its floor cost is
pure HBM bandwidth: steps x state_bytes. This microbenchmark measures
the ACHIEVED bandwidth of exactly that access pattern — a Pallas
kernel whose grid walks pod steps, double-buffering DMA copies of
node blocks into VMEM scratch and reducing them on the VPU — so the
design note can quote a measured number instead of a datasheet one.

Usage: python tools/stream_bench.py  (runs on the real TPU; exits
quietly with a note on CPU-only hosts)
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def stream_kernel(state_ref, out_ref, scratch, sem, *, n_blocks, block_rows):
    """One grid step = one pod step: stream every (block_rows, 128)
    block of the state through VMEM scratch (double-buffered) and fold
    a max-reduce — the shape of a blocked feasibility+score pass."""

    def get_copy(slot, b):
        return pltpu.make_async_copy(
            state_ref.at[pl.ds(b * block_rows, block_rows), :],
            scratch.at[slot],
            sem.at[slot],
        )

    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        out_ref[...] = jnp.zeros((1, 128), jnp.int32)

    get_copy(0, 0).start()
    acc = jnp.full((1, 128), -(2**31) + 1, jnp.int32)

    def body(b, acc):
        slot = jax.lax.rem(b, 2)
        get_copy(slot, b).wait()

        @pl.when(b + 1 < n_blocks)
        def _():
            get_copy(1 - slot, b + 1).start()

        tile = scratch[slot]
        return jnp.maximum(acc, jnp.max(tile, axis=0, keepdims=True))

    acc = jax.lax.fori_loop(0, n_blocks, body, acc)
    # accumulate across steps so no step's streaming can be elided
    out_ref[...] = out_ref[...] + acc


def run(state_mb: float, steps: int, block_rows: int = 256) -> float:
    rows = int(state_mb * 2**20) // (128 * 4)
    rows = (rows // block_rows) * block_rows
    n_blocks = rows // block_rows
    state = jnp.asarray(
        np.random.randint(0, 1 << 20, (rows, 128), dtype=np.int32)
    )

    kernel = functools.partial(
        stream_kernel, n_blocks=n_blocks, block_rows=block_rows
    )
    call = pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 128), lambda s: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 128), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((2, block_rows, 128), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    jitted = jax.jit(call)
    np.asarray(jitted(state))  # compile + full sync (a host fetch)
    t0 = time.perf_counter()
    np.asarray(jitted(state))
    dt = time.perf_counter() - t0
    gb = rows * 128 * 4 * steps / 1e9
    return gb / dt


def main() -> None:
    if jax.devices()[0].platform not in ("tpu",):
        print("no TPU backend; streaming bench skipped")
        return
    for mb in (8, 16, 32, 64):
        steps = max(1, int(2000 * 16 / mb))  # ~constant total bytes
        bw = run(mb, steps)
        print(f"state {mb:3d} MB, {steps} steps: {bw:7.1f} GB/s achieved")


if __name__ == "__main__":
    sys.exit(main())
