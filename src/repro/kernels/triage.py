"""Pallas TPU kernel: cascade triage + escalation compaction (core C1).

One pass over the whole fleet's (E, N) tick matrix produces route codes,
escalation buffer slots (stable per-row prefix-sum compaction) and each
row's escalated count, with an (E, 2) per-row runtime threshold matrix:
every edge's triage and compaction in ONE launch per scheduler tick.  A
single edge's batch is a one-row matrix (``ops.triage_fleet``).

Rows are independent, so the grid tiles them (``parallel``); each row's
lanes are walked in ``LANE_BLOCK``-wide blocks (``arbitrary``: in order),
and the row's escalation count block stays resident across them as the
compaction carry.  Inside a block the prefix sum is a matmul with an
upper-triangular ones matrix on the MXU: 0/1 operands are exact in bf16
and the f32 accumulation is exact up to 2**24, so the slots equal a
cumsum's bit for bit (Mosaic has no cumsum lowering).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

#: lanes per block along N — also the side of the prefix-sum triangle
LANE_BLOCK = 512
#: VMEM elements of one (rows, lanes) block — lanes count at least 128,
#: the vreg width a narrow block pads to: 512 KiB per f32/i32 operand, so
#: the double-buffered conf/routes/slots blocks stay a few MiB of VMEM
BLOCK_ELEMS = 1 << 17


def _triage_fleet_kernel(conf_ref, ab_ref, routes_ref, slots_ref, count_ref,
                         *, capacity: int):
    """One (rows, lanes) block of the fleet tick matrix.

    ``count_ref`` is the rows' (TR, 1) escalation count, resident across
    the lane blocks of those rows: it carries the escalations of earlier
    blocks into this block's slot numbers and ends as the row total."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        count_ref[...] = jnp.zeros_like(count_ref)

    conf = conf_ref[...]                       # (TR, TN)
    alpha = ab_ref[:, 0:1]                     # (TR, 1) broadcast over lanes
    beta = ab_ref[:, 1:2]
    routes = jnp.where(conf > alpha, 0,
                       jnp.where(conf < beta, 1, 2)).astype(jnp.int32)
    esc = routes == 2
    tn = conf.shape[1]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (tn, tn), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (tn, tn), 1))
    inclusive = jnp.dot(jnp.where(esc, 1.0, 0.0).astype(jnp.bfloat16),
                        jnp.where(tri, 1.0, 0.0).astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    pos = count_ref[...] + inclusive.astype(jnp.int32) - 1
    routes_ref[...] = routes
    slots_ref[...] = jnp.where(esc & (pos < capacity), pos, -1)
    count_ref[...] += jnp.sum(esc.astype(jnp.int32), axis=1, keepdims=True)


def triage_fleet_pallas(conf: jax.Array, thresholds: jax.Array, *,
                        capacity: int, interpret: Optional[bool] = None):
    """conf (E, N) f32, thresholds (E, 2) f32 [alpha, beta] per row ->
    (routes (E, N) i32, slots (E, N) i32, counts (E,) i32).

    The ``ops`` wrappers pad both axes to power-of-two buckets, which
    always tile; any other extent falls back to one block along it."""
    interpret = resolve_interpret(interpret)
    E, N = conf.shape
    tn = LANE_BLOCK if N % LANE_BLOCK == 0 else N
    tr = min(E, max(8, BLOCK_ELEMS // max(tn, 128)))
    tr = tr if E % tr == 0 else E
    kernel = functools.partial(_triage_fleet_kernel, capacity=capacity)
    block = pl.BlockSpec((tr, tn), lambda r, j: (r, j))
    per_row = lambda w: pl.BlockSpec((tr, w), lambda r, j: (r, 0))  # noqa: E731
    routes, slots, counts = pl.pallas_call(
        kernel,
        grid=(E // tr, N // tn),
        in_specs=[block, per_row(2)],
        out_specs=(block, block, per_row(1)),
        out_shape=(jax.ShapeDtypeStruct((E, N), jnp.int32),
                   jax.ShapeDtypeStruct((E, N), jnp.int32),
                   jax.ShapeDtypeStruct((E, 1), jnp.int32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(conf, thresholds)
    return routes, slots, counts[:, 0]
