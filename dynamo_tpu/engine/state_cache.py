"""The second kind of state: what a SLOT holds beside the paged keys.

Pages are indexed by block id and shared by content; a recurrent layer's
state belongs to one request from admission to finish, so it is indexed by
the request's slot (its row of the decode batch). ``registry.state_spec``
says what a family keeps a layer a slot (a state-space mixer: the recurrent
state in float32 and the convolution's last inputs); this module holds one
array ``[slots, *shape]`` a layer a name, on the engine's one device.

The arrays travel through every step program like the pages: taken,
returned, donated (``TpuEngine._build_programs``; a program's body writes
them through its ``mix`` as it writes the pages through ``attend``, its
epilogue never sees them). Nothing here zeroes a
slot between requests: a prompt's first chunk (``chunk_start == 0``) starts
from zeros INSIDE the prefill program (``fresh``), so a reused slot costs no
dispatch and cannot leak its last holder's state.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Spec = Tuple[Tuple[str, Tuple[int, ...], Any], ...]


class SlotState:
    """``arrays``: name -> one array a layer, each ``[slots, *shape]``."""

    def __init__(self, spec: Spec, num_layers: int, slots: int, sharding):
        self.spec, self.num_layers, self.slots = spec, num_layers, slots
        self.arrays: Dict[str, List[jax.Array]] = {
            name: [
                jax.device_put(jnp.zeros((slots, *shape), dtype), sharding)
                for _ in range(num_layers)
            ]
            for name, shape, dtype in spec
        }

    @property
    def bytes_per_slot(self) -> int:
        """Every layer's arrays of one slot."""
        return self.num_layers * sum(
            math.prod(shape) * np.dtype(dtype).itemsize
            for _, shape, dtype in self.spec
        )

    @property
    def nbytes(self) -> int:
        return self.slots * self.bytes_per_slot


def fresh(row: jax.Array, chunk_start) -> jax.Array:
    """A slot's row as a prompt's chunk finds it: zeros for the first chunk,
    what the chunk before left otherwise (a trace-time-free select)."""
    return jnp.where(chunk_start == 0, jnp.zeros_like(row), row)
