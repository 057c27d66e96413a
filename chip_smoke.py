#!/usr/bin/env python3
"""Bring-up check: the served path runs on a TPU, through the user's entry points.

    python chip_smoke.py               # one chip: demo_transformer, demo_ssm
    python chip_smoke.py --four-chips  # four chips: a 4-stage GPipe over a mesh

One chip.  Each executable zoo model is deployed with
``deploy(DeploymentSpec(model=..., codec="int8", use_pallas=True,
interpret=False))`` on a seeded 8-node edge cluster.  It serves 32 requests
through the pipelined engine with a node killed halfway, then a
``VersionBumped`` redeploys version 1 and 8 more requests are served.  Every
request must complete exactly once, and every output is compared with the
float32 reference: the same model's executor with ``use_pallas=False`` run
at ``Precision.HIGHEST`` with that version's weights.

Four chips.  ``runtime.pipeline.make_gpipe`` runs ``demo_mlp`` as four
stages on a ``stage`` mesh, in the stage order ``plan_pipeline`` places on
the chips' interconnect, with int8 boundaries through the compiled Pallas
quantize kernels.  It is compared with the same layers run in sequence on
one chip.

The error metric is each request's relative L2 error against the reference.
Its tolerance is derived from the two error sources the served path adds,
measured on the reference itself for the same inputs: an int8 round trip
(``INT8_MAX_REL_ERROR`` per element) at every layer boundary, and the chip's
default float32 matmul precision.  The tolerance is twice their sum.

Exits non-zero, printing no result line, when JAX finds no TPU or any phase
fails.  A passing run ends with one JSON line naming the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_FIRST, N_AFTER_BUMP = 32, 8
INPUT_SHAPES = {"demo_transformer": (256, 32), "demo_ssm": (8, 24)}
# requests are seeded normals at the scale of the CLI's demo request
# (``launch/serve.py``: ones * 0.1); at 0.5 these random-weight models
# amplify a 0.4% int8 hop into output errors past 100%, which no tolerance
# can separate from wrong weights
INPUT_SCALE = 0.1
F32_EPS = 2.0 ** -24  # float32 unit roundoff: the floor under any tolerance


class CompileClock:
    """Seconds JAX spent in backend compiles, from its monitoring events."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def rel_l2(y, ref):
    """Per-row relative L2 error over every axis but the first."""
    y, ref = np.asarray(y, np.float64), np.asarray(ref, np.float64)
    axes = tuple(range(1, y.ndim))
    return np.sqrt(((y - ref) ** 2).sum(axes) / (ref ** 2).sum(axes))


def tolerance(exact, with_int8, default_precision, n_ops: int) -> tuple[float, str]:
    """Twice the error the int8 hops and the chip's default matmul precision
    cause on the reference, plus float32 rounding over ``n_ops`` chained
    products (layers x width)."""
    e_int8 = float(rel_l2(with_int8, exact).max())
    e_prec = float(rel_l2(default_precision, exact).max())
    tol = 2.0 * (e_int8 + e_prec + n_ops * F32_EPS)
    return tol, (f"2 x (int8 at every boundary {e_int8:.6g} + default matmul "
                 f"precision {e_prec:.6g} + f32 rounding {n_ops * F32_EPS:.3g})")


def run_layers(executor, n_layers: int, x, codec=None):
    """The reference chain, optionally with a codec round trip at every
    layer boundary: the interior hops a plan may code (the dispatcher's
    hops in and out always ride raw)."""
    for i in range(n_layers):
        if codec is not None and i > 0:
            x = codec.transcode(x)
        x = executor(i, i + 1, x)
    return x


def stage_custom_calls(deployment) -> int:
    """``tpu_custom_call`` count in the compiled program of the first stage:
    int8 payload in, the stage's layers, int8 payload out."""
    from repro.dataplane import get_codec
    from repro.dataplane.base import EncodedActivation

    pipe = deployment.control.pipeline
    part = pipe.pods[0].partition
    codec = get_codec("int8").configured(**deployment.spec.execution().kwargs())

    def stage_program(x):
        enc = EncodedActivation(codec, codec.encode(x))
        _, q, s, _ = codec.encode(pipe.executor(part.start, part.stop, enc))
        return q, s

    shape = (deployment.spec.microbatch,) + INPUT_SHAPES[deployment.spec.model]
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    return jax.jit(stage_program).lower(x).compile().as_text().count("tpu_custom_call")


def serve_phase(clock: CompileClock, model: str, seed: int = 0) -> bool:
    from repro.api import ClusterSpec, DeploymentSpec, deploy
    from repro.cluster import NodeFailed, VersionBumped
    from repro.core import model_zoo
    from repro.dataplane import get_codec

    graph, ref_for_version = getattr(model_zoo, model)()  # knob off: jnp path
    n_layers = len(graph.layers)
    spec = DeploymentSpec(
        model=model,
        cluster=ClusterSpec(n_nodes=8, capacity_bytes=graph.total_param_bytes / 3,
                            seed=seed + 3),
        codec="int8", seed=seed, microbatch=4,
        use_pallas=True, interpret=False,
    )
    t0, c0 = time.perf_counter(), clock.total
    d = deploy(spec)
    xs = jax.random.normal(jax.random.PRNGKey(seed),
                           (N_FIRST + N_AFTER_BUMP, *INPUT_SHAPES[model])) * INPUT_SCALE
    first = [d.submit(xs[i]) for i in range(N_FIRST)]
    victim = None
    while d.loop.backlog or d.pending:
        if victim is None and len(d.loop.completed) >= N_FIRST // 2:
            pods = d.control.pipeline.pods
            victim = pods[1 if len(pods) > 1 else 0].node_id
            d.inject(NodeFailed(victim))
        d.step()
    path_before_bump = list(d.control.pipeline.path())
    d.inject(VersionBumped(1))
    second = [d.submit(xs[N_FIRST + i]) for i in range(N_AFTER_BUMP)]
    d.drain()
    jax.block_until_ready([r.result for r in d.loop.completed])
    serve_s, serve_compile_s = time.perf_counter() - t0, clock.total - c0

    submitted = sorted(r.req_id for r in first + second)
    done = sorted(r.req_id for r in d.loop.completed)
    actions = [a.kind for a in d.control.history]
    ok = (done == submitted and not d.loop.failed and victim is not None
          and "replace" in actions and "redeploy" in actions
          and d.observed().version == 1)
    print(f"[{model}] {len(done)}/{len(submitted)} requests completed, "
          f"{len(d.loop.failed)} failed; node {victim} killed after "
          f"{N_FIRST // 2}, path {path_before_bump} -> v1 on "
          f"{list(d.control.pipeline.path())}; link codecs {list(d.plan.codecs)}; "
          f"reconcile actions {actions}")
    print(f"[{model}] serving wall {serve_s:.3f} s, of which backend compile "
          f"{serve_compile_s:.3f} s")

    int8_ref = get_codec("int8")
    for version, x, reqs in ((0, xs[:N_FIRST], first), (1, xs[N_FIRST:], second)):
        ex = ref_for_version(version)
        with jax.default_matmul_precision("highest"):
            exact = run_layers(ex, n_layers, x)
            with_int8 = run_layers(ex, n_layers, x, codec=int8_ref)
        default = run_layers(ex, n_layers, x)
        tol, why = tolerance(exact, with_int8, default,
                             n_ops=n_layers * INPUT_SHAPES[model][-1])
        err = float(rel_l2(jnp.stack([r.result for r in reqs]), exact).max())
        good = err <= tol
        ok &= good
        print(f"[{model}] v{version}: {len(reqs)} outputs, max rel L2 error "
              f"vs float32 HIGHEST reference {err:.6g} "
              f"{'<=' if good else '>'} tolerance {tol:.6g} = {why}")

    n_custom = stage_custom_calls(d)
    ok &= n_custom > 0
    print(f"[{model}] tpu_custom_call in the compiled stage-0 program "
          f"(layers {d.control.pipeline.pods[0].partition.start}-"
          f"{d.control.pipeline.pods[0].partition.stop - 1}): {n_custom}")
    print(f"[{model}] {'PASS' if ok else 'FAIL'}")
    return ok


def ici_bandwidth(devices):
    """Relative link bandwidth between chips: 1 over the interconnect hop
    count between their coordinates (0 on the diagonal)."""
    n = len(devices)
    bw = np.zeros((n, n))
    for i, a in enumerate(devices):
        for j, b in enumerate(devices):
            if i != j:
                bw[i, j] = 1.0 / sum(abs(p - q) for p, q in zip(a.coords, b.coords))
    return bw


def gpipe_phase(clock: CompileClock, seed: int = 0) -> bool:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import model_zoo
    from repro.core.execution import ExecutionKnob
    from repro.dataplane import get_codec
    from repro.runtime.pipeline import make_gpipe, plan_pipeline, reorder_stage_params

    mesh = jax.make_mesh((4,), ("stage",), devices=jax.devices()[:4])
    devices = list(mesh.devices.flat)  # mesh position order: the plan's pods
    graph, ex_for_version = model_zoo.demo_mlp()
    n_layers, d = len(graph.layers), 32
    ws = model_zoo.demo_mlp_weights(0)
    link_bw = ici_bandwidth(devices)
    plan = plan_pipeline(graph, 4, stage_capacity=2 * graph.layers[0].param_bytes,
                         pod_bw=link_bw)
    hops = [round(1.0 / link_bw[a, b])
            for a, b in zip(plan.stage_order, plan.stage_order[1:])]
    print(f"[gpipe] plan: cuts {plan.cuts}, stage order {plan.stage_order}, "
          f"interconnect hops per boundary {hops}")
    bounds = [0, *(c + 1 for c in plan.cuts), n_layers]
    per_stage = {bounds[j + 1] - bounds[j] for j in range(4)}
    if len(per_stage) != 1:
        print(f"[gpipe] plan cuts {plan.cuts} are not uniform; make_gpipe "
              f"needs equal stages")
        return False
    lps = per_stage.pop()
    stage_ws = jnp.asarray(ws.reshape(4, lps, d, d))
    placed = jax.device_put(reorder_stage_params(stage_ws, plan),
                            NamedSharding(mesh, P("stage")))
    on_device = {}
    for shard in placed.addressable_shards:
        on_device[shard.index[0].start] = shard.device
    for j, pos in enumerate(plan.stage_order):
        dev = on_device[pos]
        print(f"[gpipe] stage {j} (layers {bounds[j]}-{bounds[j + 1] - 1}) on "
              f"mesh position {pos}: {dev}")
    distinct = len({on_device[pos].id for pos in plan.stage_order})

    def stage_fn(local_w, x):
        for i in range(lps):
            x = jnp.tanh(x @ local_w[i])
        return x

    n_micro = 8
    x = jax.random.normal(jax.random.PRNGKey(seed), (n_micro, 16, d)) * INPUT_SCALE
    runs, runs_on_kernels = {}, True
    for name, kw in (("plain", {}),
                     ("int8", dict(compress=True, quant_block=d,
                                   execution=ExecutionKnob(use_pallas=True)))):
        pipe = jax.jit(make_gpipe(stage_fn, mesh, n_micro=n_micro,
                                  stage_order=plan.stage_order, **kw))
        c0 = clock.total
        runs[name] = np.asarray(pipe(placed, x))
        text = pipe.lower(placed, x).compile().as_text()
        n_custom = text.count("tpu_custom_call")
        runs_on_kernels &= name == "plain" or n_custom > 0
        print(f"[gpipe] {name}: compile {clock.total - c0:.3f} s, "
              f"tpu_custom_call {n_custom}")

    # the same layers in sequence on one chip; the int8 variant round-trips
    # the activation at the three stage boundaries the pipeline codes
    ex = ex_for_version(0)
    codec = get_codec("int8").configured(block=d)
    xf = jax.device_put(x.reshape(-1, d), jax.devices()[0])

    def sequence(with_int8: bool):
        y = xf
        for j in range(4):
            y = ex(bounds[j], bounds[j + 1], y)
            if with_int8 and j < 3:
                y = codec.transcode(y)
        return np.asarray(y).reshape(n_micro, 16, d)

    with jax.default_matmul_precision("highest"):
        exact, exact_int8 = sequence(False), sequence(True)
    one_chip = sequence(False)  # the chip's default matmul precision
    ok = distinct == 4 and runs_on_kernels and max(hops) == 1
    for name, with_int8 in (("plain", exact), ("int8", exact_int8)):
        tol, why = tolerance(exact, with_int8, one_chip, n_ops=n_layers * d)
        err = float(rel_l2(runs[name], one_chip).max())
        good = err <= tol
        ok &= good
        print(f"[gpipe] {name}: max rel L2 error vs the one-chip sequence "
              f"{err:.6g} {'<=' if good else '>'} tolerance {tol:.6g} = {why}")
    print(f"[gpipe] stages on {distinct} distinct devices; "
          f"{'PASS' if ok else 'FAIL'}")
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-stage GPipe phase across four chips")
    args = ap.parse_args()
    try:
        from repro.launch.compile_cache import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from "
              f"{Path(__file__).resolve().parent / 'src'}: {e}", file=sys.stderr)
        return 2
    print(f"compile cache: {configure_compile_cache()}")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 1
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX runs on "
              f"{devices[0].platform}); this check does not run elsewhere",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"devices: {len(devices)} x {devices[0].device_kind}")

    clock = CompileClock()
    phases = ([lambda: gpipe_phase(clock)] if args.four_chips else
              [lambda m=m: serve_phase(clock, m) for m in INPUT_SHAPES])
    ok = True
    for phase in phases:
        try:
            ok &= phase()
        except Exception:
            traceback.print_exc()
            ok = False
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
