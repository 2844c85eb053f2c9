"""Faults planted in the timed path, to show that the output check catches
them. The CPU tests plant them at a tiny size, ``control.py --fault`` at a
cell's own size on the chip; a benchmark run never plants one.

- ``state_unchanged``: the decode step returns the cache it was given, so
  no decoded token is ever written to it;
- ``token_altered``: the decode step's tokens are shifted by one id where
  they are produced;
- ``exchange_left_out`` (a cell on a mesh): the decode step runs as if no
  chip exchanged its partial results: every parameter the engine splits
  over the mesh's ``model`` axis keeps only its first shard and reads 0
  in the others, so each product summed over the chips is the first
  chip's share alone, as that chip would see it without the all-reduce.
"""
from __future__ import annotations

from typing import Callable

FAULTS = ("state_unchanged", "token_altered")
# faults only a cell on a mesh can have
MESH_FAULTS = ("exchange_left_out",)


def first_shard_only(params):
    """``params`` with every axis split over the mesh's ``model`` axis
    kept in its first shard and zero in the others (in the same
    shardings)."""
    import jax
    import jax.numpy as jnp

    def one(x, sh):
        n = sh.mesh.shape["model"]
        for dim, axes in enumerate(sh.spec):
            if axes == "model" or (isinstance(axes, tuple)
                                   and "model" in axes):
                keep = jnp.arange(x.shape[dim]) < x.shape[dim] // n
                shape = [1] * x.ndim
                shape[dim] = x.shape[dim]
                x = jnp.where(keep.reshape(shape), x, jnp.zeros((), x.dtype))
        return x
    shardings = jax.tree.map(lambda x: x.sharding, params)
    return jax.jit(lambda p: jax.tree.map(one, p, shardings),
                   out_shardings=shardings)(params)


def broken(build: Callable, fault: str, vocab: int) -> Callable:
    """``build`` (``drive.build_engine``), but the engines it builds have
    ``fault`` in their decode step."""
    if fault not in FAULTS + MESH_FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: "
                         f"{FAULTS + MESH_FAULTS}")

    def build_broken(*a, **k):
        eng = build(*a, **k)
        step = eng._step
        if fault in MESH_FAULTS and eng.mesh is None:
            raise ValueError(f"{fault} needs a cell on a mesh")
        own = {}

        def bad_step(params, state, *rest, **kw):
            if fault == "exchange_left_out":
                if "params" not in own:
                    own["params"] = first_shard_only(params)
                params = own["params"]
            new_state, lanes, tok, emitted, done = step(params, state, *rest,
                                                        **kw)
            if fault == "state_unchanged":
                new_state = state
            elif fault == "token_altered":
                tok = (tok + 1) % vocab
            return new_state, lanes, tok, emitted, done
        eng._step = bad_step
        return eng
    return build_broken
