"""The state arena: what a model's RECURRENT layers keep for a sequence,
beside the paged K/V pool of its attention layers.

A recurrent layer (``LayerSpec(mixer="kda")``) keeps no keys and values: it
keeps one fixed-size state a sequence, whatever the sequence's length, so
there is nothing to page.  The arena holds ONE ROW A DECODE SLOT and
recurrent layer::

    state (R, S, H, d_k, d_v) float32     the delta rule's matrix a head
    tail  (R, S, taps - 1, channels)      the last inputs of the depthwise
                                          convolution (q, k and v channels)

``R`` the model's recurrent layers in plan order
(``TransformerLM.state_layers``), ``S`` the engine's slots.  The rows are
placed, donated and handed back like the pool's arenas: every step program
takes ``pool.arenas + state.arenas`` last, donated.  A prefill writes its
slot's rows (:func:`write_slot`), a chunk's continuation reads them as its
initial state (:func:`read_slot`), the decode step updates the rows of the
slots that decode and leaves an idle slot's row as it is (on a TPU through
``ops.kda_step``: the arena aliased through a Pallas kernel that reads each
active slot's state once and writes it once, where it lies).  A row belongs
to its slot: admission overwrites it whole, so completion frees nothing.

There is no sharing: a prefix's state exists only at the position it was
computed to, so the radix prefix cache (which hands a request K/V blocks it
did not compute) is off for a model with recurrent layers; state snapshots
at block boundaries are ROADMAP M6's.
"""
from __future__ import annotations


class StateArena:
    """The two arenas for ``n_layers`` recurrent layers and ``slots`` decode
    slots.  ``state_shape`` (H, d_k, d_v) and ``tail_shape`` (taps - 1,
    channels) are ``TransformerLM.state_shapes``; ``tail_dtype`` is the
    dtype the model computes its projections in."""

    def __init__(self, *, n_layers: int, slots: int, state_shape, tail_shape,
                 tail_dtype):
        import jax.numpy as jnp

        self.n_layers, self.slots = int(n_layers), int(slots)
        self.state = jnp.zeros((self.n_layers, self.slots) + tuple(state_shape),
                               jnp.float32)
        self.tail = jnp.zeros((self.n_layers, self.slots) + tuple(tail_shape),
                              tail_dtype)

    @property
    def arenas(self) -> tuple:
        """``(state, tail)``: what the donated executables take behind the
        pool's arenas and hand back (assign their outputs here)."""
        return (self.state, self.tail)

    @arenas.setter
    def arenas(self, new) -> None:
        self.state, self.tail = new

    @property
    def arena_bytes(self) -> int:
        return sum(a.size * a.dtype.itemsize for a in self.arenas)

    @property
    def row_bytes(self) -> int:
        """One slot's row in one recurrent layer: state and tail."""
        return self.arena_bytes // (self.n_layers * self.slots)


def read_slot(state, tail, slot):
    """One slot's rows as a batch of one: -> (state (R, 1, H, d_k, d_v),
    tail (R, 1, taps - 1, channels)); ``slot`` may be traced."""
    from jax import lax
    return (lax.dynamic_slice_in_dim(state, slot, 1, axis=1),
            lax.dynamic_slice_in_dim(tail, slot, 1, axis=1))


def write_slot(state, tail, new_state, new_tail, slot):
    """Overwrite one slot's rows (the shapes :func:`read_slot` hands out)
    -> (state, tail); jitted by the engine with both arenas donated."""
    from jax import lax
    return (lax.dynamic_update_slice_in_dim(state, new_state.astype(state.dtype),
                                            slot, axis=1),
            lax.dynamic_update_slice_in_dim(tail, new_tail.astype(tail.dtype),
                                            slot, axis=1))
