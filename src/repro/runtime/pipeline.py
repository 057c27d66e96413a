"""GPipe pipeline over a mesh axis with SEIFER cuts + compressed boundaries.

This is the paper's technique as a first-class TPU feature:

  * **cuts** come from ``core.partitioner`` on the arch's exported
    LayerGraph (min-bottleneck contiguous cuts under per-stage memory),
  * **placement** of stages onto pods comes from ``core.placement`` on the
    ICI/DCN bandwidth table -- the heaviest boundary rides the fastest link,
  * **boundary transport** is ``jax.lax.ppermute`` inside ``shard_map``
    (the FIFO+TCP analogue), optionally int8-compressed
    (``kernels/quantize`` -- the ZFP/LZ4 analogue), halving DCN bytes.

GPipe schedule: ``n_micro + n_stages - 1`` ticks; stage s computes microbatch
``t - s`` at tick t.  Steady-state period = max(stage compute, link time) --
literally the paper's bottleneck-latency objective.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.bottleneck import evaluate_pipeline
from repro.core.execution import ExecutionKnob
from repro.core.graph import LayerGraph
from repro.core.partitioner import partition_exact_k
from repro.core.placement import CommGraph, place_optimal
from repro.dataplane.base import EncodedActivation
from repro.kernels.quantize import dequantize_int8, quantize_int8


# ---------------------------------------------------------------------------
# Planning: SEIFER cuts + stage->pod placement
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PipelinePlan:
    n_stages: int
    cuts: tuple[int, ...]  # layer-graph edges cut
    stage_order: tuple[int, ...]  # stage i runs on pod stage_order[i]
    bottleneck_bytes: float
    est_bottleneck_s: float
    # steady-state GPipe period under the serving engine's timing model
    # (max over stage compute and link times); 1/est_period_s is the
    # pipeline's predicted per-microbatch throughput once full
    est_period_s: float = 0.0


def plan_pipeline(
    graph: LayerGraph,
    n_stages: int,
    *,
    stage_capacity: float,
    pod_bw: np.ndarray | None = None,
    device_flops: float | Sequence[float] | None = None,
) -> PipelinePlan:
    """Cut the layer graph and place stages on the pod graph.

    ``pod_bw``: (n_stages, n_stages) inter-pod bandwidth (bytes/s).  Defaults
    to a DCN ring.  Placement maximizes throughput by matching the heaviest
    boundaries to the fastest links (exact min-bottleneck path).

    ``device_flops`` (per-pod compute rate) feeds the same
    ``core.bottleneck.service_times`` model the edge serving engine uses, so
    ``est_period_s`` is comparable across the TPU and edge backends.
    """
    part = partition_exact_k(graph, int(stage_capacity), n_stages)
    if not part.feasible:
        raise ValueError(
            f"model does not fit {n_stages} stages of {stage_capacity/1e9:.1f} GB"
        )
    if pod_bw is None:
        pod_bw = np.full((n_stages, n_stages), 6.25e9)
        np.fill_diagonal(pod_bw, 0.0)
    comm = CommGraph(bw=pod_bw, node_capacity=np.full(n_stages, stage_capacity))
    place = place_optimal(
        list(part.boundaries), [p.param_bytes for p in part.partitions], comm
    )
    if not place.feasible:
        raise ValueError("no feasible stage placement on the pod graph")
    # ONE steady-state definition: est_period_s IS
    # core.bottleneck.PipelineMetrics.pipeline_period on the same inputs --
    # max over every serial resource (stage compute times and link
    # latencies), the cadence of a full pipe.  tests/test_pipeline_multidev.py
    # pins the two against each other so they cannot drift apart again.
    metrics = evaluate_pipeline(
        part.partitions, place.path, comm, device_flops=device_flops
    )
    return PipelinePlan(
        n_stages=n_stages,
        cuts=part.cuts,
        stage_order=place.path,
        bottleneck_bytes=float(max(part.boundaries, default=0)),
        est_bottleneck_s=float(place.bottleneck_latency),
        est_period_s=float(metrics.pipeline_period),
    )


# ---------------------------------------------------------------------------
# GPipe execution inside shard_map
# ---------------------------------------------------------------------------

def make_gpipe(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    *,
    axis: str = "stage",
    n_micro: int,
    compress: bool = False,
    quant_block: int = 256,
    stage_order: tuple[int, ...] | None = None,
    execution: ExecutionKnob | None = None,
):
    """Build a pipelined forward: (stage_params, x (n_micro, mb, ...)) -> y.

    ``stage_params`` leaves have a leading ``n_stages`` dim in MESH order
    (sharded over ``axis``; use ``reorder_stage_params`` to realize a SEIFER
    placement); ``x`` is replicated; the output (n_micro, ...) is the last
    LOGICAL stage's rows, replicated on every device of ``axis``.

    ``stage_order[j]`` = mesh position hosting logical stage j; the
    ppermute route follows it, so the heaviest boundary rides the link the
    placement chose.

    ``execution`` (``repro.core.execution.ExecutionKnob``) selects the
    quantize path for the compressed send -- the same knob a
    ``DeploymentSpec`` threads to the edge engines' codecs.
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    order = list(stage_order) if stage_order is not None else list(range(n_stages))
    perm = [(order[j], order[j + 1]) for j in range(n_stages - 1)]
    # logical stage index of each mesh position
    logical = np.argsort(np.asarray(order))
    ex_kw = execution.kwargs() if execution is not None else {}

    def _send(x):
        if not compress:
            return jax.lax.ppermute(x, axis, perm)
        q, s = quantize_int8(x, quant_block, **ex_kw)
        q = jax.lax.ppermute(q, axis, perm)
        s = jax.lax.ppermute(s, axis, perm)
        return dequantize_int8(q, s, dtype=x.dtype, **ex_kw)

    def pipe(stage_params, x):
        local = jax.tree.map(lambda t: t[0], stage_params)  # strip stage dim
        stage = jnp.asarray(logical)[jax.lax.axis_index(axis)]
        mb_shape = x.shape[1:]
        buf = jnp.zeros(mb_shape, x.dtype)  # incoming activation
        outs = jnp.zeros((n_micro,) + mb_shape, x.dtype)

        def tick(carry, t):
            buf, outs = carry
            feed = jax.lax.dynamic_index_in_dim(
                x, jnp.clip(t, 0, n_micro - 1), keepdims=False
            )
            inp = jnp.where(stage == 0, feed, buf)
            active = (t - stage >= 0) & (t - stage < n_micro)
            y = stage_fn(local, inp)
            y = jnp.where(active, y, jnp.zeros_like(y))
            out_t = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            is_out = (stage == n_stages - 1) & (t >= n_stages - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(is_out, y, outs[out_t]),
                out_t, 0,
            )
            buf = _send(y)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(
            tick, (buf, outs), jnp.arange(n_micro + n_stages - 1)
        )
        # only the last logical stage ever writes ``outs`` (the others keep
        # zeros), so the sum over the stage axis is exact and leaves the
        # rows replicated -- valid under Auto and Explicit mesh axes alike
        return jax.lax.psum(outs, axis)

    # the scan carry turns stage-varying after the first tick, so the
    # varying-manual-axes check cannot type it
    return jax.shard_map(pipe, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=P(), check_vma=False)


# ---------------------------------------------------------------------------
# Edge-cluster bridge: run the same stage execution through simulated pods
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StageCounts:
    """What a layer executor did: stage programs traced (cache misses),
    calls served by a compiled program, and calls run layer by layer."""

    traces: int = 0
    compiled_calls: int = 0
    eager_calls: int = 0


@dataclasses.dataclass(frozen=True)
class StageProgram:
    """One layer range compiled for one input: ``fn`` is the ``jax.jit``
    of ``seifer_stage``, which evaluates the range's traced jaxpr with its
    constants (the weights) passed in as ``consts``, never embedded."""

    fn: Callable
    consts: tuple
    out_tree: Any

    def __call__(self, x: jax.Array):
        return jax.tree.unflatten(self.out_tree, self.fn(self.consts, x))


def make_layer_executor(layer_fns: list[Callable[[jax.Array], jax.Array]]):
    """Adapt per-layer callables into the cluster ``ExecutorFn`` signature.

    The edge control plane's ``InferencePipeline`` drives pods with
    ``executor(start, stop, x)`` over the partition's layer range -- this is
    the bridge that lets the TPU-side stage functions (or any per-layer jnp
    closures) serve through the simulated pod chain, so the serving loop's
    microbatches exercise identical math on both backends.

    **Compiled stages.**  A ``jax.Array`` input runs the range as ONE
    compiled program, so XLA fuses the layers' elementwise passes and the
    call returns once the work is dispatched.  Programs are cached per
    ``(start, stop, x.shape, x.dtype, weak type)`` and the trace context
    that changes the traced program (the default matmul precision, x64).
    On a miss the range is traced once with ``jax.make_jaxpr``; its
    constants -- the weights the layer fns close over -- are hoisted into
    arguments of a ``jax.jit`` named ``seifer_stage`` (embedded, JAX would
    write them into the program as literals).  Device-array constants are
    passed by reference; host (numpy) constants are uploaded once per
    executor, memoised by their host buffer.  Host inputs keep the eager
    layer-by-layer loop, so numpy codecs and host-side callers see what
    they always did.  ``executor.counts`` (``StageCounts``) counts traces,
    compiled calls and eager calls; ``executor.program(start, stop, x)``
    returns the cached ``StageProgram`` (tracing it on a miss).

    **Fused decode protocol.**  A layer fn may carry a ``fused`` attribute --
    a ``{codec_name: handler}`` dict whose handler consumes a still-encoded
    boundary activation (``dataplane.base.EncodedActivation``) directly,
    e.g. int8 wire payloads feeding ``kernels.quantize.dequant_matmul``
    instead of a separate dequantize pass.  The executor advertises
    ``executor.fused_codecs`` -- codec names EVERY layer can consume, so the
    engine's gating stays correct for any partition cut point -- and
    transparently falls back to ``EncodedActivation.decode()`` when the
    entry layer has no handler.  The handler (or the decode) runs before,
    and outside, the compiled program of the remaining layers.
    """
    fused_codecs: frozenset[str] | None = None
    for fn in layer_fns:
        keys = frozenset(getattr(fn, "fused", {}) or {})
        fused_codecs = keys if fused_codecs is None else fused_codecs & keys

    def run_layers(start: int, stop: int, x):
        for i in range(start, stop):
            x = layer_fns[i](x)
        return x

    counts = StageCounts()
    programs: dict[tuple, StageProgram] = {}
    uploaded: dict[tuple, tuple[np.ndarray, jax.Array]] = {}

    def device_const(c):
        if isinstance(c, jax.Array):
            return c
        host = np.asarray(c)
        # the buffer's address names it while ``uploaded`` keeps it alive;
        # the zoo's ``w[i]`` is a fresh view of one array on every trace
        key = (host.__array_interface__["data"][0], host.shape, host.strides,
               host.dtype.str)
        if key not in uploaded:
            uploaded[key] = (host, jax.device_put(host))
        return uploaded[key][1]

    def program(start: int, stop: int, x) -> StageProgram:
        key = (start, stop, x.shape, x.dtype, jax.typeof(x).weak_type,
               jax.config.jax_default_matmul_precision,
               jax.config.jax_enable_x64)
        prog = programs.get(key)
        if prog is None:
            closed, out_shape = jax.make_jaxpr(
                partial(run_layers, start, stop), return_shape=True)(x)
            jaxpr = closed.jaxpr

            def seifer_stage(consts, x):
                return jax.core.eval_jaxpr(jaxpr, consts, x)

            prog = programs[key] = StageProgram(
                jax.jit(seifer_stage),
                tuple(device_const(c) for c in closed.consts),
                jax.tree.structure(out_shape))
            counts.traces += 1
        return prog

    def executor(start: int, stop: int, x):
        if isinstance(x, EncodedActivation):
            handler = None
            if start < stop:
                handler = getattr(layer_fns[start], "fused", {}).get(x.codec.name)
            if handler is not None:
                x = handler(x)
                start += 1
            else:
                x = x.decode()
        if start >= stop:
            return x
        if isinstance(x, jax.Array):
            counts.compiled_calls += 1
            return program(start, stop, x)(x)
        counts.eager_calls += 1
        return run_layers(start, stop, x)

    executor.fused_codecs = fused_codecs or frozenset()
    executor.counts = counts
    executor.program = program
    return executor


def reorder_stage_params(stage_params: Any, plan: PipelinePlan) -> Any:
    """Permute logically-ordered stage params into mesh order.

    Input leaves are stacked in LOGICAL stage order; mesh position p must
    hold logical stage argsort(stage_order)[p] so that, combined with the
    route in ``make_gpipe``, logical stage j physically runs on pod
    ``plan.stage_order[j]``.
    """
    inv = np.argsort(np.asarray(plan.stage_order))
    return jax.tree.map(lambda t: t[inv], stage_params)
