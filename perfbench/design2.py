"""The paper's Design #2 approximate 8x8 multiplier, written out gate by
gate for the plain reference (no lookup table, no import of the program).

Design #2 is Design #1 with the six least significant product columns
truncated: partial products a_j & b_i with i + j < 6 are never formed.
Stage #1 reduces columns 6..9 with the proposed inexact multicolumn
cells (3,3:2 without and with carry-in, 1,3:2) and columns 10..13 with a
chain of exact 4:2 compressors; Stage #2 runs two 3,3:2 cells over
columns (6, 7) and (8, 9), chained carry-out to carry-in, and a
ripple-carry adder from column 10 up.

``product(a, b)`` works elementwise on broadcastable integer arrays
(numpy or jax) holding values in [0, 255] and returns the approximate
product.  Only &, |, ^, >> and + are used, so a jitted caller evaluates
it on the vector unit without a gather.
"""
from __future__ import annotations

# Stage-1 placement of Design #2: (cell, column).  Cells take their
# "a" bits from column k and their "b" bits from column k + 1.
_STAGE1 = (("332", 6), ("132", 6), ("332c", 7), ("332c", 8), ("132", 9))
_TRUNC = 6


def _fa(x, y, z):
    s = x ^ y
    return s ^ z, (x & y) | (z & s)


def _ha(x, y):
    return x ^ y, x & y


def product(a, b):
    """Design #2 approximate product of ``a`` and ``b`` (values 0..255)."""
    abit = [(a >> j) & 1 for j in range(8)]
    bbit = [(b >> i) & 1 for i in range(8)]
    cols = {k: [] for k in range(17)}
    for i in range(8):                      # b bit i, a bit j
        for j in range(8):
            if i + j >= _TRUNC:
                cols[i + j].append(abit[j] & bbit[i])

    def pop(k, n):
        out = cols[k][:n]
        del cols[k][:n]
        assert len(out) == n
        return out

    # Stage #1: inexact multicolumn cells on columns 6..10
    for cell, k in _STAGE1:
        if cell == "332":                   # 3,3:2 without carry-in
            a1, a2, a3 = pop(k, 3)
            b1, b2, b3 = pop(k + 1, 3)
            sa, ca = _fa(a1, a2, a3)
            sb, cb = _fa(b1, b2, b3)
            cols[k].append(sa)
            cols[k + 1].append(ca | sb)
            cols[k + 2].append(cb)
        elif cell == "332c":                # 3,3:2 with carry-in
            a1, a2, a3, cin = pop(k, 4)
            b1, b2, b3 = pop(k + 1, 3)
            sa, ca = _fa(a1, a2, a3)
            sb, cb = _fa(b1, b2, b3)
            s, c_lo = _ha(sa, cin)
            cols[k].append(s)
            cols[k + 1].append(ca | c_lo | sb)
            cols[k + 2].append(cb)
        else:                               # 1,3:2, carry-in tied to 0
            a1, a2, a3 = pop(k, 3)
            (b1,) = pop(k + 1, 1)
            sa, ca = _fa(a1, a2, a3)
            cols[k].append(sa)
            cols[k + 1].append(ca | b1)

    # Stage #1: exact 4:2 chain on columns 10..13 (Fig. 8(c)-(g))
    x = pop(10, 4)
    s1, chain = _fa(x[0], x[1], x[2])
    s, carry = _ha(s1, x[3])               # first 4:2, carry-in 0
    cols[10].append(s)
    cols[11].append(carry)
    x = pop(11, 4)
    s1, cout = _fa(x[0], x[1], x[2])
    s, held = _fa(s1, x[3], chain)
    chain = cout
    cols[11].append(s)
    x = pop(12, 3)
    s1, cout = _fa(x[0], x[1], x[2])
    s, held = _fa(s1, held, chain)
    chain = cout
    cols[12].append(s)
    x = pop(13, 2)
    s, c = _fa(x[0], x[1], held)
    cols[13].append(s)
    cols[14].append(c)
    cols[13].append(chain)

    # Stage #2: 3,3:2 cells at (6, 7) and (8, 9), then ripple-carry
    F = {}
    cout = 0
    for k in (6, 8):
        a = cols[k] + [0] * (3 - len(cols[k]))
        bb = cols[k + 1] + [0] * (3 - len(cols[k + 1]))
        assert len(a) == 3 and len(bb) == 3
        sa, ca = _fa(a[0], a[1], a[2])
        sb, cb = _fa(bb[0], bb[1], bb[2])
        s, c_lo = _ha(sa, cout)
        F[k], F[k + 1] = s, ca | c_lo | sb
        cout = cb
    carries = [cout]
    for k in range(10, 16):
        bits = cols[k] + carries
        carries = []
        while len(bits) > 1:
            if len(bits) >= 3:
                s, c = _fa(bits[0], bits[1], bits[2])
                bits = bits[3:] + [s]
            else:
                s, c = _ha(bits[0], bits[1])
                bits = bits[2:] + [s]
            carries.append(c)
        F[k] = bits[0] if bits else 0

    out = 0
    for k, bit in F.items():
        out = out + (bit << k)
    return out
