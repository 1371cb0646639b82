"""Operations and bytes of the program's kernels, from the shapes of one
call as the device trace names it.

The membership kernel (``probe_call``, a ``tpu_custom_call``) answers Bp
queries against R sorted regions whose entries are W int32 words each.  Its
operands are the segment ids (SMEM), the live-lane counts [R, Bp, 1], the
query words [W, Bp, 1] and R*W word arrays [S, SEG] left in HBM; it returns
two [Bp, 1] hit counts.  Per query, region and word it copies one SEG-word
segment row from HBM: those rows are what the probe needs to read, and
with the small operands and the outputs they are its bytes.  It does no
arithmetic worth a FLOP count (one compare per copied word).
"""
from __future__ import annotations

import re

import numpy as np

SHAPE = re.compile(r"\b[su](8|16|32|64)\[([\d,]*)\]")


def _shapes(text: str):
    """(bits, dims) of every array shape in an HLO fragment."""
    out = []
    for bits, dims in SHAPE.findall(text):
        d = [int(x) for x in dims.split(",") if x]
        out.append((int(bits), d))
    return out


def member_bytes(op: str) -> int:
    """Bytes one membership-kernel call moves between HBM and the core,
    from the call's text in the trace (``%probe_call.N = (...) custom-call(
    ...)``).  Raises ValueError on a text it cannot read."""
    head, _, rest = op.partition(" custom-call(")
    args = rest.split("), custom_call_target")[0]
    outs, ins = _shapes(head), _shapes(args)
    if len(ins) < 4 or len(outs) != 2:
        raise ValueError(f"not a membership-kernel call: {op[:200]}")
    (_, seg), (_, nlive), (_, qw) = ins[0], ins[1], ins[2]
    regions = ins[3:]
    R, Bp, W = nlive[0], nlive[1], qw[0]
    if len(regions) != R * W or any(len(d) != 2 for _, d in regions):
        raise ValueError(f"membership-kernel operands do not match: "
                         f"{op[:200]}")
    seg_len = regions[0][1][1]
    rows = Bp * R * W * seg_len * 4
    small = sum(int(np.prod(d)) * b // 8 for b, d in ins[:3] + outs)
    return rows + small
