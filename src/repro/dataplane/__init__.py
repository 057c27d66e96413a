"""Bandwidth-aware activation data plane: pluggable inter-stage codecs.

SEIFER pipelines on edge networks are link-bound -- the inter-partition
activation transfer, not compute, sets the bottleneck period -- and the
companion DEFER paper shows lossy activation compression is the lever that
restores throughput.  This package is that lever as a subsystem:

  * ``registry`` -- ``@register_codec`` named-codec registry with
    did-you-mean errors (mirrors ``repro.api.registry``);
  * ``base``     -- the ``Codec`` interface: real encode/decode transforms,
    an exact ``compressed_bytes(shape, dtype)`` layout model, the analytic
    ``wire_bytes`` ratio the byte-counted simulator charges, and an
    encode/decode compute-cost model;
  * ``codecs``   -- ``identity`` / ``fp16`` / ``int8`` (backed by the
    ``kernels/quantize`` Pallas stack, numpy twin off the Pallas path) /
    ``topk-sparse``;
  * ``hops``     -- the hop model: ``build_hops`` turns a placed pipeline
    into one ``Hop`` record per link (endpoints, raw and wire bytes, codec,
    ``encode + transfer(compressed) + decode`` window), the records every
    timing, codec choice and link report reads;
  * ``auto``     -- per-link codec selection under a per-link
    ``accuracy_tolerance``, used by the planner's joint codec x placement
    search and provably never worse than ``identity``.

The codec names flow spec -> plan -> pipeline -> engine: the planner picks
(or is told) a codec per link, ``build_hops`` charges the codec's window to
the link, and the serving engine applies the real transform to every
microbatch crossing that link -- the first place the Pallas quantize kernel
participates in the serving path.
"""

from repro.dataplane.auto import assign_link_codecs, select_codec
from repro.dataplane.base import Codec
from repro.dataplane.hops import (
    Hop,
    build_hops,
    hop_bytes,
    link_charge_s,
    resolve_codecs,
)
from repro.dataplane.registry import (
    AUTO,
    UnknownCodecError,
    codec_table,
    default_codec,
    get_codec,
    list_codecs,
    register_codec,
)

__all__ = [
    "AUTO",
    "Codec",
    "Hop",
    "UnknownCodecError",
    "assign_link_codecs",
    "build_hops",
    "codec_table",
    "default_codec",
    "get_codec",
    "hop_bytes",
    "link_charge_s",
    "list_codecs",
    "register_codec",
    "resolve_codecs",
    "select_codec",
]
