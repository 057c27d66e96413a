"""GPipe pipeline correctness on a faked 4-device host (subprocess, so the
main test process keeps its single-device view)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.runtime.pipeline import make_gpipe, plan_pipeline, reorder_stage_params
    from repro.core.graph import chain

    mesh = jax.make_mesh((4,), ("stage",))
    d, n_micro = 32, 8
    ws = jax.random.normal(jax.random.PRNGKey(0), (8, d, d), jnp.float32) * 0.1
    stage_ws = ws.reshape(4, 2, d, d)

    def stage_fn(local_w, x):
        for i in range(2):
            x = jnp.tanh(x @ local_w[i])
        return x

    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, 16, d), jnp.float32)
    ref = x
    for i in range(8):
        ref = jnp.tanh(ref @ ws[i])

    g = chain("mlp", [(d * d * 4, 16 * d * 4)] * 8)
    pod_bw = np.array(
        [[0, 10e9, 1e9, 1e9], [10e9, 0, 5e9, 1e9],
         [1e9, 5e9, 0, 2e9], [1e9, 1e9, 2e9, 0]], float)
    plan = plan_pipeline(g, 4, stage_capacity=2 * d * d * 4, pod_bw=pod_bw)
    assert plan.cuts == (1, 3, 5), plan.cuts  # balanced SEIFER cuts

    # identity placement, exact
    pipe = make_gpipe(stage_fn, mesh, axis="stage", n_micro=n_micro)
    with mesh:
        y = pipe(stage_ws, x)
    assert float(jnp.max(jnp.abs(y - ref))) < 1e-6, "identity placement"

    # SEIFER placement, exact
    pipe = make_gpipe(stage_fn, mesh, axis="stage", n_micro=n_micro,
                      stage_order=plan.stage_order)
    with mesh:
        y = pipe(reorder_stage_params(stage_ws, plan), x)
    assert float(jnp.max(jnp.abs(y - ref))) < 1e-6, "seifer placement"

    # int8-compressed boundaries: small bounded error
    pipe = make_gpipe(stage_fn, mesh, axis="stage", n_micro=n_micro,
                      compress=True, quant_block=32,
                      stage_order=plan.stage_order)
    with mesh:
        y = pipe(reorder_stage_params(stage_ws, plan), x)
    err = float(jnp.max(jnp.abs(y - ref)))
    assert 0 < err < 0.05, f"compressed pipeline err {err}"

    # same int8 boundaries through the Pallas kernels (interpret mode): the
    # execution knob reaches the quantized send path, and the kernel emits
    # the same codes as the jnp oracle, so the outputs agree to fp noise
    from repro.core.execution import PALLAS_INTERPRET
    pipe = make_gpipe(stage_fn, mesh, axis="stage", n_micro=n_micro,
                      compress=True, quant_block=32,
                      stage_order=plan.stage_order, execution=PALLAS_INTERPRET)
    with mesh:
        y2 = pipe(reorder_stage_params(stage_ws, plan), x)
    knob_err = float(jnp.max(jnp.abs(y2 - y)))
    assert knob_err < 1e-6, f"pallas-interpret knob diverged: {knob_err}"
    print("PIPELINE_OK")
    """
)


def test_gpipe_four_stages():
    repo = Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        # the child stays on the CPU: it must never load the TPU library
        env={**os.environ, "PYTHONPATH": str(repo / "src"),
             "JAX_PLATFORMS": "cpu"},
        cwd=repo,
    )
    assert "PIPELINE_OK" in proc.stdout, proc.stdout + proc.stderr


def test_plan_period_is_bottleneck_pipeline_period():
    """plan_pipeline.est_period_s IS core.bottleneck's pipeline_period on the
    same partitions/path/comm -- ONE steady-state definition shared with the
    edge serving engine, pinned here so the two cannot drift apart."""
    import numpy as np

    from repro.core.bottleneck import evaluate_pipeline
    from repro.core.graph import chain
    from repro.core.partitioner import partition_exact_k
    from repro.core.placement import CommGraph
    from repro.runtime.pipeline import plan_pipeline

    d = 32
    g = chain("mlp", [(d * d * 4, 16 * d * 4)] * 8)
    pod_bw = np.array(
        [[0, 10e9, 1e9, 1e9], [10e9, 0, 5e9, 1e9],
         [1e9, 5e9, 0, 2e9], [1e9, 1e9, 2e9, 0]], float)
    cap = 2 * d * d * 4
    plan = plan_pipeline(g, 4, stage_capacity=cap, pod_bw=pod_bw,
                         device_flops=1e9)
    part = partition_exact_k(g, cap, 4)
    comm = CommGraph(bw=pod_bw, node_capacity=np.full(4, float(cap)))
    metrics = evaluate_pipeline(part.partitions, list(plan.stage_order), comm,
                                device_flops=1e9)
    assert plan.est_period_s == float(metrics.pipeline_period)
    assert plan.est_period_s > 0.0
    # the period dominates the pure link bottleneck (it maxes over links AND
    # stage compute), never undercuts it
    assert plan.est_period_s >= plan.est_bottleneck_s
