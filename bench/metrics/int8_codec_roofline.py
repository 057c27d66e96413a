"""Share of their roofline that the int8 link codec's Pallas kernels
(``quantize_int8`` on encode, ``dequantize_int8`` on decode) reach."""

from bench.roofline import nbytes, share


def quantize_ops_bytes(rows: int, d: int, block: int, in_bytes: int = 2) -> tuple[float, float]:
    """Encode: read x (rows, d), write int8 q (rows, d) and one f32 scale
    per ``block`` elements; about four operations an element (abs, max,
    divide, round).  Bound by memory."""
    nb = -(-d // block)
    return float(4 * rows * d), float(rows * d * (in_bytes + 1) + rows * nb * 4)


def dequantize_ops_bytes(rows: int, d: int, block: int, out_bytes: int = 2) -> tuple[float, float]:
    """Decode: read int8 q and the scales, write x; one multiply an element."""
    nb = -(-d // block)
    return float(rows * d), float(rows * d * (1 + out_bytes) + rows * nb * 4)


def _match(name, outs, ins):
    return name in ("quantize_int8", "dequantize_int8")


def _cost(outs, ins, run):
    if ins[0][0] == "s8":  # decode: (q, scales) -> x
        rows, d = ins[0][1]
        nb = ins[1][1][-1]
        return dequantize_ops_bytes(rows, d, -(-d // nb), nbytes((outs[0][0], (1,))))
    rows, d = ins[0][1]  # encode: x -> (q, scales)
    nb = outs[1][1][-1]
    return quantize_ops_bytes(rows, d, -(-d // nb), nbytes((ins[0][0], (1,))))


def read(run):
    return share(run, _match, _cost)
