"""State pool: one slot of constant size a sequence, beside the page pool.

The second kind of cache of a model whose layers are not all attention
over a growing context (:mod:`paddle_tpu.models.phi4flash`): a
state-space layer keeps a state and the tail of its convolution, a
window-attention layer the K/V rows of its last ``window`` positions.
None of it grows with the sequence, so a sequence needs exactly one slot
from admission to release and nothing is handed back and forth between
sequences; the :class:`~.kv_pool.PagePool` beside it holds only the
layers whose cache does grow (for Phi-4-mini-flash one layer of 32).

Device arrays, ``n_slots + 1`` slots each (slot 0 is the **sink**, as page
0 is the page pool's: a decode bucket's idle rows gather from it and
scatter into it, so every index a compiled step computes is in bounds
and no two live rows ever write one slot):

- ``ssm``   ``[n_ssm, slots, d_state, d_inner]`` float32: states on the
  second-minor axis, channels on the lanes, the layout the scan kernel
  and the chip's tiles want (``[.., d_inner, 16]`` would be padded eight
  times over in HBM);
- ``conv``  ``[n_ssm, slots, d_conv - 1, d_inner]``: the convolution's
  tail, in the activations' type;
- ``win_k``, ``win_v`` ``[n_window, slots * ring_pages, page_size,
  row_width]`` (a pool of rows, as the page pool beside it): the window
  layers' rows **laid out as fixed pages**, a ring a slot: position ``p`` lies in row ``p % window`` of
  the slot's ``ring_pages = window / page_size`` pages, which are pages
  ``slot * ring_pages ...`` of every layer. So the paged decode kernel
  reads a window layer as it reads the page pool (``layer`` = the window
  layer, a table that follows from the slot, the length ``min(len,
  window)``: rows below it are exactly the live ones, in any order), and
  nothing gathers a slot's rows into a batch. A ring needs no slack
  beyond the window and no reset: a row is read only once this sequence
  has written it.

Only the state and the tail are zeroed when a slot is taken, and inside
the first chunk's program (a flag it carries), not by a host-side write
of the slot's megabytes. The arrays are updated *functionally*, as the
page pool's: the engine passes them into its jitted step (donated on
TPU), gets the new arrays back and rebinds them via :meth:`bind`.

**Why a slot a sequence is safe.** With ``n_slots`` = the engine's widest
decode bucket = the scheduler's ``max_concurrency``, admission holds
``running + prefilling + migrating_in < max_concurrency`` before it calls
``prefill_begin``, and ``release`` frees the slot before the count
falls: :meth:`alloc` can never find the pool empty under the scheduler,
and raises :class:`StatePoolFull` if driven otherwise.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = ["StatePool", "StatePoolFull"]


class StatePoolFull(RuntimeError):
    """No slot is free: more sequences were admitted than the pool has
    slots (the scheduler's admission rule makes that impossible where
    ``n_slots`` is its ``max_concurrency``)."""


class StatePool:
    SINK = 0    # reserved slot of idle bucket rows, never allocated
    ARRAYS = ("ssm", "conv", "win_k", "win_v")

    def __init__(self, n_slots, *, n_ssm, d_state, d_inner, d_conv,
                 n_window, window, page_size, row_width, dtype="float32"):
        if n_slots < 1:
            raise ValueError(f"n_slots {n_slots} must be >= 1")
        if window % page_size:
            raise ValueError(f"the window ({window}) must be whole pages "
                             f"({page_size})")
        self.n_slots = int(n_slots)
        self.ring_pages = window // page_size
        slots = self.n_slots + 1
        self.ssm = jnp.zeros((n_ssm, slots, d_state, d_inner), jnp.float32)
        self.conv = jnp.zeros((n_ssm, slots, d_conv - 1, d_inner), dtype)
        ring = (n_window, slots * self.ring_pages, page_size, row_width)
        self.win_k = jnp.zeros(ring, dtype)
        self.win_v = jnp.zeros(ring, dtype)
        # LIFO free list, deterministic: lowest slots hand out first
        self._free = list(range(self.n_slots, 0, -1))
        self._slots: dict = {}      # seq_id -> slot
        self._peak = 0
        self._resets = 0

    # ------------------------------------------------------------ arrays
    def arrays(self) -> tuple:
        return tuple(getattr(self, name) for name in self.ARRAYS)

    def bind(self, ssm, conv, win_k, win_v):
        """Rebind the device arrays a step returned."""
        self.ssm, self.conv, self.win_k, self.win_v = ssm, conv, win_k, win_v

    @property
    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in self.arrays()))

    @property
    def bytes_per_slot(self) -> int:
        return self.nbytes // (self.n_slots + 1)

    # ------------------------------------------------------- bookkeeping
    @property
    def slots_in_use(self) -> int:
        return len(self._slots)

    def alloc(self, seq_id) -> int:
        """Take a slot for ``seq_id``; its state is zeroed by the first
        chunk's program (counted in ``resets``)."""
        if seq_id in self._slots:
            raise ValueError(f"sequence {seq_id!r} already holds a slot")
        if not self._free:
            raise StatePoolFull(
                f"all {self.n_slots} state slots are taken: admission "
                f"must hold live sequences under the slots")
        slot = self._slots[seq_id] = self._free.pop()
        self._peak = max(self._peak, len(self._slots))
        self._resets += 1
        return slot

    def free(self, seq_id):
        """Hand ``seq_id``'s slot back (a sequence that holds none is
        left alone: release may follow a failed admission)."""
        slot = self._slots.pop(seq_id, None)
        if slot is not None:
            self._free.append(slot)

    def slot(self, seq_id) -> int:
        return self._slots[seq_id]

    def slots_array(self, seq_ids) -> np.ndarray:
        """Slots of ``seq_ids`` as int32; ``None`` entries (a bucket's
        idle rows) are the sink."""
        return np.asarray([self.SINK if s is None else self._slots[s]
                           for s in seq_ids], np.int32)

    def stats(self) -> dict:
        return {"slots": self.n_slots, "slots_in_use": self.slots_in_use,
                "slots_peak": self._peak,
                "bytes_per_slot": self.bytes_per_slot,
                "state_bytes": self.nbytes, "resets": self._resets}
