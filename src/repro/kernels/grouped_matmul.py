"""Grouped matmul over rows sorted by group — the experts' farm workers as
one kernel (JAX's megablox ``gmm``, a Pallas TPU kernel).

``lhs`` holds rows sorted by group: the first ``sizes[0]`` rows belong to
group 0, the next ``sizes[1]`` to group 1, and so on; rows past
``sizes.sum()`` belong to none.  Each row is multiplied by its group's
matrix of ``rhs``.  The grid visits only the row tiles of groups that have
rows (the forward pass builds its schedule with
``visit_empty_groups=False``), so an empty group's weight tiles are never
read and the unused tail is never computed: its output rows are left
undefined, and the caller reads only the grouped rows.  The backward pass
is megablox's own (``ops.gmm``'s custom VJP).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.ops import gmm

from .backend import default_interpret

ROW_TILE = 128      # one MXU pass of rows
WIDE_TILE = 1024    # a 1024 x 1024 bf16 weight tile is 2 MB of VMEM


def _tile(dim: int) -> int:
    return dim if dim <= WIDE_TILE else WIDE_TILE


def grouped_matmul(lhs, rhs, sizes, *, interpret: Optional[bool] = None):
    """lhs (m, k), rhs (groups, k, n), sizes (groups,) int32 ->
    (m, n) in ``lhs.dtype``, accumulated in float32.  ``interpret=None``
    resolves via :mod:`kernels.backend`: Mosaic on TPU, the Python
    interpreter elsewhere."""
    m, k = lhs.shape
    pad = -m % ROW_TILE
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    # positional: the custom VJP's static arguments
    out = gmm(lhs, rhs, sizes.astype(jnp.int32), lhs.dtype,
              (ROW_TILE, _tile(k), _tile(rhs.shape[2])), None, None, False,
              default_interpret(interpret))
    return out[:m]
