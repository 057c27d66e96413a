"""Share of its roofline that the Pallas flash-attention kernel reaches."""

from bench.roofline import nbytes, share


def flash_ops_bytes(bh: int, bkh: int, s: int, hd: int, causal: bool = True,
                    elem_bytes: int = 2) -> tuple[float, float]:
    """Operations and bytes of one self-attention call.

    ``bh`` query rows of heads (batch x heads) and ``bkh`` key/value rows
    (batch x kv heads), ``s`` positions, head size ``hd``.  QK^T and PV
    are 2 s^2 hd each per query head; causal attention needs the lower
    triangle, half of it.  Bytes: q, k, v read once and o written once.
    """
    pairs = s * s / 2 if causal else s * s
    ops = 2 * 2 * bh * pairs * hd
    io = (2 * bh + 2 * bkh) * s * hd * elem_bytes
    return float(ops), float(io)


def _match(name, outs, ins):
    """The kernel is the one custom call taking (q, k, v) as three rank-3
    operands with equal sequence and head sizes and returning q's shape."""
    if len(ins) != 3 or len(outs) != 1 or any(len(t[1]) != 3 for t in ins):
        return False
    (_, q), (_, k), (_, v) = ins
    return (outs[0][1] == q and k == v and q[1:] == k[1:] and q[0] % k[0] == 0)


def _cost(outs, ins, run):
    (dt, (bh, s, hd)), (_, (bkh, _, _)) = ins[0], ins[1]
    return flash_ops_bytes(bh, bkh, s, hd, causal=True, elem_bytes=nbytes((dt, (1,))))


def read(run):
    return share(run, _match, _cost)
