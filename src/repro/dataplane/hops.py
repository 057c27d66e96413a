"""The hop model: what each link of a placed pipeline carries and costs.

A pipeline of ``k`` stages on ``path`` has ``k + 1`` hops.  Hop 0 runs from
the dispatcher to stage 0, hop h (1 <= h <= k-1) from stage h-1 to stage h,
and hop k from the last stage back to the dispatcher.  ``build_hops`` is the
one place that turns a placed pipeline into one ``Hop`` record per hop:
its endpoints, the raw and on-wire bytes, the codec riding it, and the
serial window it occupies,

    encode(raw bytes on the sender) + wire_bytes / bandwidth
        + decode(raw bytes on the receiver)

The planner's prediction (``core.bottleneck``), the codec choice
(``dataplane.auto``), the serving engine's virtual clock and link report,
the control plane's bottleneck and the critical-path analysis all read
these records.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

from repro.dataplane.base import Codec
from repro.dataplane.registry import default_codec, get_codec


def node_flops(flops_per_node, node: int | None) -> float:
    """Resolve a scalar-or-per-node flops model for one node (0 = unmodelled).

    The single dispatch point for ``flops_per_node``: stage compute times
    and the codec cost model both price compute through it."""
    if flops_per_node is None or node is None:
        return 0.0
    if np.isscalar(flops_per_node):
        return float(flops_per_node)
    return float(flops_per_node[node])


def resolve_codecs(codecs) -> list[Codec] | None:
    """Names-or-instances -> instances (None passes through)."""
    if codecs is None:
        return None
    return [c if isinstance(c, Codec) else get_codec(c) for c in codecs]


def link_charge_s(
    codec: Codec,
    nbytes: float,
    bw: float,
    *,
    src_flops: float = 0.0,
    dst_flops: float = 0.0,
) -> float:
    """The serial window one ``nbytes`` transfer occupies on the link."""
    if nbytes <= 0:
        return 0.0
    wire = codec.wire_bytes(nbytes)
    xfer = float("inf") if bw <= 0 else wire / bw
    return codec.encode_cost_s(nbytes, src_flops) + xfer + \
        codec.decode_cost_s(nbytes, dst_flops)


def split_hop(link_s: float, codec, raw_bytes: int,
              src_flops: float = 0.0, dst_flops: float = 0.0):
    """Analytic ``(encode_s, wire_s, decode_s)`` decomposition of one hop.

    Uses the codec cost model ``link_charge_s`` charges, so the three
    parts sum to the hop's total service time.  Codec-free hops are pure
    wire; dead links (inf) stay pure wire so the infinity never leaks into
    encode/decode.
    """
    link_s = float(link_s)
    if codec is None or not math.isfinite(link_s):
        return (0.0, link_s, 0.0)
    enc = float(codec.encode_cost_s(raw_bytes, src_flops))
    dec = float(codec.decode_cost_s(raw_bytes, dst_flops))
    wire = max(0.0, link_s - enc - dec)
    return (enc, wire, dec)


def hop_bytes(boundaries: Sequence[float], in_bytes: float = 0.0,
              out_bytes: float = 0.0) -> list[float]:
    """Raw bytes per hop: the request in, each stage boundary, the reply."""
    return [in_bytes, *boundaries, out_bytes]


@dataclasses.dataclass(frozen=True)
class Hop:
    """One hop of a placed pipeline.

    ``codec`` is ``None`` exactly when nothing crosses a wire (no bytes, a
    missing endpoint, or colocated endpoints); such a hop has zero bytes and
    zero ``seconds``.  ``parts`` splits ``seconds`` into its
    ``(encode_s, wire_s, decode_s)`` windows."""

    src: int | None
    dst: int | None
    raw_bytes: float
    codec: Codec | None
    wire_bytes: float
    seconds: float
    parts: tuple[float, float, float]


def build_hops(
    hop_bytes: Sequence[float],
    path: Sequence[int],
    bw: np.ndarray,
    *,
    dispatcher: int | None = None,
    flops_per_node=None,
    codecs: Sequence | None = None,
) -> list[Hop]:
    """One ``Hop`` per hop of ``path`` (``len(path) + 1`` records).

    ``hop_bytes`` are the raw bytes in hop order (``hop_bytes()``).
    ``codecs`` holds one ``Codec`` or registered name per hop; ``None``
    rides the registry default (``identity``, the raw wire) on every hop.
    """
    k = len(path)
    if len(hop_bytes) != k + 1:
        raise ValueError(f"expected {k + 1} hop byte counts, got {len(hop_bytes)}")
    if codecs is None:
        codecs = [default_codec()] * (k + 1)
    codecs = resolve_codecs(codecs)
    if len(codecs) != k + 1:
        raise ValueError(f"expected {k + 1} hop codecs, got {len(codecs)}")
    out = []
    for h in range(k + 1):
        src = dispatcher if h == 0 else path[h - 1]
        dst = dispatcher if h == k else path[h]
        raw = float(hop_bytes[h])
        if raw <= 0 or src is None or dst is None or src == dst:
            out.append(Hop(src, dst, 0.0, None, 0.0, 0.0, (0.0, 0.0, 0.0)))
            continue
        codec = codecs[h]
        src_flops = node_flops(flops_per_node, src)
        dst_flops = node_flops(flops_per_node, dst)
        seconds = link_charge_s(codec, raw, float(bw[src, dst]),
                                src_flops=src_flops, dst_flops=dst_flops)
        out.append(Hop(
            src, dst, raw, codec, codec.wire_bytes(raw), seconds,
            split_hop(seconds, codec, raw,
                      src_flops=src_flops, dst_flops=dst_flops),
        ))
    return out
