"""Share of its roofline that the Pallas SSD scan (``ssd_chunked``) reaches."""

from bench.roofline import nbytes, share


def ssd_ops_bytes(b: int, s: int, h: int, dh: int, n: int, chunk: int,
                  x_bytes: int = 2) -> tuple[float, float]:
    """Operations and bytes the chunked SSD needs for one call.

    Shapes: x (b, s, h, dh), B and C (b, s, n) shared by the heads (one
    group), chunks of ``chunk`` steps.  Per chunk: C B^T once per sequence
    (2 q^2 n), the masked scores times x per head (2 q^2 dh), the carried
    state read out per head (2 q n dh) and updated per head (2 q n dh).
    Bytes: x once (``x_bytes`` per element), B and C once (f32), dt and its
    cumulative sum once per head and position (f32), y written once (f32).
    """
    q = min(chunk, s)
    nc = s // q
    ops = b * nc * 2 * q * q * n + b * h * nc * (2 * q * q * dh + 4 * q * n * dh)
    io = (b * s * h * dh * x_bytes + 2 * b * s * n * 4 + 2 * b * h * s * 4
          + b * s * h * dh * 4)
    return float(ops), float(io)


def _match(name, outs, ins):
    return name.startswith("ssd_chunked") and len(ins) == 7


def _cost(outs, ins, run):
    bh, s, dh = ins[0][1]
    b, _, n = ins[1][1]
    x_bytes = nbytes((ins[0][0], (1,)))
    return ssd_ops_bytes(b, s, bh // b, dh, n, run.cfg["assumed"]["kernel_chunk"], x_bytes)


def read(run):
    return share(run, _match, _cost)
