"""Local sort ops.

The reference wraps Spark's sort-shuffle writers for the local sort/spill
(writer/wrapper/RdmaWrapperShuffleWriter.scala:83-99) and Spark's
ExternalSorter on the reduce side (scala/RdmaShuffleReader.scala:100-114).
The TPU equivalents are on-device sorts feeding / draining the exchange.

``lax.sort`` lowers to XLA's bitonic/variadic sort, which tiles well on TPU;
multi-operand form co-sorts payload with keys without materializing a
gather.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
from jax import lax


def sort_kv(keys: jnp.ndarray, values: Optional[jnp.ndarray] = None,
            ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Sort rows by key; values (any shape with matching leading axis) ride
    along. Returns (sorted_keys, sorted_values)."""
    if values is None:
        return lax.sort(keys), None
    if values.ndim == 1:
        sk, sv = lax.sort((keys, values), num_keys=1)
        return sk, sv
    # Multi-column payload: sort an index array, then gather.
    idx = jnp.arange(keys.shape[0], dtype=jnp.int32)
    sk, sidx = lax.sort((keys, idx), num_keys=1)
    return sk, jnp.take(values, sidx, axis=0)


def sort_segments(keys: jnp.ndarray, valid: jnp.ndarray,
                  values: Optional[jnp.ndarray] = None):
    """Sort only the valid rows of a padded buffer: invalid rows are pushed
    to the end by keying them with the dtype max. Standard trick for
    fixed-capacity exchange outputs where ``recv_total <= capacity``."""
    sentinel = jnp.array(jnp.iinfo(keys.dtype).max, dtype=keys.dtype)
    masked = jnp.where(valid, keys, sentinel)
    return sort_kv(masked, values)
