"""Pallas dispatch gate shared by the simulator-facing kernels.

The netsim hot-path kernels (`plb_select.plane_split`,
`jsq_route.pair_fractions`) have two implementations: a Pallas kernel
(TPU) and a pure-jnp fallback (`ref.py` — also the test oracle).  On
CPU/GPU the fallback is both faster and bit-identical to the engine's
historical math, so Pallas is enabled only when the default JAX backend
is TPU, unless `REPRO_NETSIM_PALLAS` forces it (1/0).
"""
from __future__ import annotations

import math
import os
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

LANES = 128
SUBLANES = 8


def pallas_enabled(override: Optional[bool] = None) -> bool:
    """Whether simulator kernels should lower through Pallas."""
    if override is not None:
        return override
    env = os.environ.get("REPRO_NETSIM_PALLAS")
    if env is not None:
        return env.lower() in ("1", "true", "t", "yes", "y", "on")
    return jax.default_backend() == "tpu"


def pallas_interpret(override: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel must run in interpret mode: required on
    every non-TPU backend (`pallas_call` without `interpret=True` fails
    off-TPU).  Kernel entry points resolve this when their `interpret`
    argument is None, so `REPRO_NETSIM_PALLAS=1` exercises the kernel
    bodies on CPU CI without per-call-site plumbing."""
    if override is not None:
        return override
    return jax.default_backend() != "tpu"


def to_lanes(arrays: Sequence[jax.Array], br: int
             ) -> Tuple[List[jax.Array], int, int]:
    """Lay same-size arrays out for an elementwise kernel as float32
    `(rows, 128)` slabs, zero-padded so `rows` is a multiple of the
    block rows.  Returns `(slabs, block_rows, n_blocks)`; block rows
    are `br` (a multiple of 8), shrunk to the slab for small inputs, so
    every block is (8, 128)-tile aligned as Mosaic requires."""
    if br % SUBLANES:
        raise ValueError(f"block rows {br} are not a multiple of "
                         f"{SUBLANES}")
    n = arrays[0].size
    rows = -(-max(n, 1) // LANES)
    br = min(br, -(-rows // SUBLANES) * SUBLANES)
    rows = -(-rows // br) * br
    pad = rows * LANES - n
    slabs = [jnp.pad(a.reshape(-1).astype(jnp.float32), (0, pad))
             .reshape(rows, LANES) for a in arrays]
    return slabs, br, rows // br


def from_lanes(slab: jax.Array, shape, dtype) -> jax.Array:
    """Inverse of `to_lanes` for one output slab."""
    return slab.reshape(-1)[:math.prod(shape)].reshape(shape).astype(dtype)
