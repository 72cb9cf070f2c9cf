"""Faults planted in the timed path, to show that ``correct`` sees them.

A benchmark run never plants one: ``run.py --fault <kind>``,
``control.py`` and the tests do.

Served tokens (check.py's gap_max):

  state     the decode step returns the state it was given (no cache row
            appended, no position advanced)
  half      half of the slots left out: their tokens are copies of the
            other half's
  token     the token altered where it is produced (next id)
  control   the 4-bit control in the program's place: at each served
            position the token it puts first is judged in place of the
            program's (check.compare, ``control_in_place``)

The Design #2 qdot (qdot_check.py's qdot_gap):

  gather    the stage-2 gather reads the table at (b, a) for (a, b)
  nocomp    the mean-field compensation left out
  residual  the program's rank-r emulation of the table in place of the
            exact gather (its own lower-precision path)

One chip, one program: there is no exchange between chips to leave out.
"""
from __future__ import annotations

import contextlib

STEP = ("state", "half", "token")
QDOT = ("gather", "nocomp", "residual")
KINDS = STEP + ("control",) + QDOT


def break_step(server, kind: str) -> None:
    """Replace ``server.step`` by a broken one (after set-up)."""
    step = server.step
    vocab = server.cfg["vocab_size"]
    B = server.slots

    def broken(params, state, tok):
        t, lg, new = step(params, state, tok)
        if kind == "state":
            return t, lg, state
        if kind == "half":
            return t.at[B // 2:].set(t[:B - B // 2]), lg, new
        return (t + 1) % vocab, lg, new
    server.step = broken


@contextlib.contextmanager
def table(kind: str | None):
    """While open, the program's delta tables are read transposed when
    ``kind`` is ``gather`` (the tables are constants of the programs
    traced inside)."""
    if kind != "gather":
        yield
        return
    from repro.kernels import ops
    orig = ops.get_delta_lut

    def transposed(design, signed=False):
        return orig(design, signed).T.copy()
    ops.get_delta_lut = transposed
    try:
        yield
    finally:
        ops.get_delta_lut = orig
