"""Public ops for postings packing: jit'd wrappers that dispatch to the
compiled Pallas kernel on TPU or the pure-jnp reference elsewhere
(identical math, XLA-fused). Packed blocks come back in the device
layout, (nb, 128) words with lane ``4 * plane + word``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.postings_pack import ref
from repro.kernels.postings_pack.kernel import pack_pallas, unpack_pallas

BLOCK = ref.BLOCK


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@jax.jit
def pack(deltas: jnp.ndarray):
    """deltas: (nb, 128) uint32 -> (packed (nb, 128), bw (nb,))."""
    if _on_tpu():
        return pack_pallas(deltas, interpret=False)
    # pack_ref, not pack_fast: XLA fuses the broadcast form into one pass,
    # which wins at the large nb of whole-segment builds (the transpose
    # form wins for the small-nb unpacks of the query path).
    packed, bw = ref.pack_ref(deltas)
    return packed.reshape(deltas.shape[0], BLOCK), bw


@jax.jit
def unpack(packed: jnp.ndarray, bw: jnp.ndarray):
    """(nb, 128) packed words + (nb,) -> (nb, 128) uint32 values."""
    if _on_tpu():
        return unpack_pallas(packed, bw, interpret=False)
    return ref.unpack_fast(packed.reshape(-1, 32, ref.WORDS_PER_PLANE), bw)


packed_bytes = ref.packed_bytes
