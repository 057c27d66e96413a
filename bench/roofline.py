"""A kernel's share of its roofline, from its trace events and shapes.

For each event of the kernel the least time the chip could take is the
larger of its operations over the peak FLOP/s and its bytes over the peak
HBM bandwidth (``peaks.py``).  The share is the sum of those least times
over the sum of the events' device times, in percent.  The operations and
bytes are what the algorithm needs at the event's shapes (each metric file
keeps its kernel's function); a share above 100% means they are counted
too high, or the events leave out part of the kernel's time.
"""

from __future__ import annotations


def share(run, match, cost) -> float | None:
    """``match(name, outs, ins)`` picks the kernel's events; ``cost(outs,
    ins, run)`` gives ``(ops, bytes)`` of one event.  None when the traced
    window holds no such event."""
    if run.device is None:
        return None
    events = run.device.kernel_ops(match)
    spent = sum(e["seconds"] for e in events)
    if not events or spent <= 0:
        return None
    flops, bw = run.peaks["bf16_flops"], run.peaks["hbm_bytes_per_s"]
    need = 0.0
    for e in events:
        ops, nbytes = cost(e["outs"], e["ins"], run)
        need += max(ops / flops, nbytes / bw)
    return 100.0 * need / spent


def nbytes(shape) -> int:
    """Bytes of one ``(dtype, dims)`` shape from a trace event."""
    size = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
            "u32": 4, "f32": 4}[shape[0]]
    n = 1
    for d in shape[1]:
        n *= d
    return n * size
