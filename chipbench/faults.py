"""Faults planted in the timed path, to show that the output check catches
them. The CPU tests plant them at a tiny size, ``control.py --fault`` at a
cell's own size on the chip; a benchmark run never plants one.

- ``state_unchanged``: the decode step returns the cache it was given, so
  no decoded token is ever written to it;
- ``token_altered``: the decode step's tokens are shifted by one id where
  they are produced.
"""
from __future__ import annotations

from typing import Callable

FAULTS = ("state_unchanged", "token_altered")


def broken(build: Callable, fault: str, vocab: int) -> Callable:
    """``build`` (``drive.build_engine``), but the engines it builds have
    ``fault`` in their decode step."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")

    def build_broken(*a, **k):
        eng = build(*a, **k)
        step = eng._step

        def bad_step(params, state, *rest, **kw):
            new_state, lanes, tok, emitted, done = step(params, state, *rest,
                                                        **kw)
            if fault == "state_unchanged":
                new_state = state
            else:
                tok = (tok + 1) % vocab
            return new_state, lanes, tok, emitted, done
        eng._step = bad_step
        return eng
    return build_broken
