"""Compiled stage programs in ``runtime.pipeline.make_layer_executor``.

A ``jax.Array`` input runs the layer range as one ``jax.jit`` program,
traced once per (range, input shape and dtype, matmul precision), with the
weights the layer fns close over passed as arguments; host (numpy) inputs
keep the eager layer-by-layer loop.  These tests pin the compiled path to
the eager one, the cache to its key, and the program to a size that shows
no weight was embedded as a literal.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ClusterSpec, DeploymentSpec, deploy
from repro.core import model_zoo
from repro.dataplane import get_codec
from repro.dataplane.base import EncodedActivation
from repro.runtime.pipeline import make_layer_executor

# (zoo model, one request's activation shape)
MODELS = {
    "demo_mlp": (32,),
    "demo_ssm": (8, 24),
    "demo_transformer": (256, 32),
}


def _ranges(n):
    return [(0, n), (1, n - 1), (0, 1), (n - 1, n)]


def _input(shape, batch=3, seed=1):
    return np.asarray(jax.random.normal(jax.random.PRNGKey(seed),
                                        (batch, *shape))) * 0.5


@pytest.mark.parametrize("model", sorted(MODELS))
def test_compiled_stage_matches_eager_loop(model):
    graph, efv = getattr(model_zoo, model)()
    ex = efv(0)
    x = _input(MODELS[model])
    for start, stop in _ranges(len(graph.layers)):
        eager = ex(start, stop, x)  # numpy input: the layer-by-layer loop
        compiled = ex(start, stop, jnp.asarray(x))
        # XLA reorders f32 sums once it fuses: a few 1e-5 over 4 layers
        np.testing.assert_allclose(np.asarray(compiled), np.asarray(eager),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{model} layers {start}-{stop}")
    n = len(_ranges(len(graph.layers)))
    assert (ex.counts.traces, ex.counts.compiled_calls, ex.counts.eager_calls) == (n, n, n)


@pytest.mark.parametrize("start", [0, 1, 3])
def test_fused_int8_entry_then_compiled_layers(start):
    """The fused dequant-matmul handler runs on the entry layer outside the
    program; the rest of the range is one compiled program, and the result
    is decode-then-run's."""
    graph, efv = model_zoo.demo_transformer()
    ex = efv(0)
    n = len(graph.layers)
    codec = get_codec("int8")
    enc = EncodedActivation(codec, codec.encode(jnp.asarray(_input((256, 32), batch=2))))
    fused = ex(start, n, enc)
    decoded = ex(start, n, np.asarray(enc.decode()))  # eager, unfused
    np.testing.assert_allclose(np.asarray(fused), np.asarray(decoded),
                               rtol=1e-5, atol=1e-5)
    assert ex.counts.traces == (1 if start + 1 < n else 0)
    assert ex.counts.eager_calls == 1


def test_second_call_with_the_same_key_traces_nothing():
    graph, efv = model_zoo.demo_ssm()
    ex = efv(0)
    x = jnp.asarray(_input((8, 24)))
    first = ex(1, 4, x)
    assert ex.counts.traces == 1
    again = ex(1, 4, x + 0.0)
    other = ex(1, 4, x[:2])  # another batch size is another program
    assert ex.counts.traces == 2
    ex(1, 4, x[:2])
    assert ex.counts.traces == 2 and ex.counts.compiled_calls == 4
    np.testing.assert_array_equal(np.asarray(first), np.asarray(again))
    np.testing.assert_allclose(np.asarray(other), np.asarray(first[:2]),
                               rtol=1e-6, atol=1e-6)


def test_host_input_takes_the_eager_loop_and_returns_what_it_did():
    ws = np.random.default_rng(0).normal(size=(3, 8, 8)).astype(np.float32)
    fns = [lambda x, w=ws[i]: np.tanh(x @ w) for i in range(3)]
    ex = make_layer_executor(fns)
    x = np.ones((2, 8), np.float32)
    want = x
    for f in fns:
        want = f(want)
    got = ex(0, 3, x)
    assert isinstance(got, np.ndarray)
    np.testing.assert_array_equal(got, want)
    assert (ex.counts.traces, ex.counts.compiled_calls, ex.counts.eager_calls) == (0, 0, 1)


def test_matmul_precision_is_part_of_the_key():
    """``chip_smoke.py`` calls one executor under
    ``default_matmul_precision("highest")`` after default-precision calls:
    each gets a program traced under its own precision."""
    w = jnp.asarray(np.random.default_rng(1).normal(size=(16, 16)), jnp.float32)
    ex = make_layer_executor([lambda x: x @ w, lambda x: jnp.tanh(x) @ w])
    x = jnp.ones((4, 16), jnp.float32)
    ex(0, 2, x)
    default_text = ex.program(0, 2, x).fn.lower(ex.program(0, 2, x).consts, x).as_text()
    with jax.default_matmul_precision("highest"):
        got = ex(0, 2, x)
        prog = ex.program(0, 2, x)
        highest_text = prog.fn.lower(prog.consts, x).as_text()
        want = jnp.dot(jnp.tanh(jnp.dot(x, w, precision="highest")), w,
                       precision="highest")
    assert ex.counts.traces == 2
    assert "HIGHEST" in highest_text and "HIGHEST" not in default_text
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_weights_are_arguments_not_literals():
    """A stage closing over 8 MB of weights lowers to a small program: the
    weights are arguments, host ones uploaded once and shared by every
    range, device ones passed by reference."""
    rng = np.random.default_rng(2)
    host = (rng.normal(size=(2, 1024, 1024)) / 32).astype(np.float32)  # 8 MB
    dev = jnp.asarray(rng.normal(size=(1024, 1024)) / 32, jnp.float32)
    ex = make_layer_executor([
        lambda x: x @ host[0],  # a fresh view of ``host`` on every trace
        lambda x: jnp.tanh(x @ host[1]),
        lambda x: x @ dev,
    ])
    x = jnp.ones((4, 1024), jnp.float32)
    whole = ex.program(0, 3, x)
    text = whole.fn.lower(whole.consts, x).as_text()
    assert len(text) < 64 * 1024, len(text)
    tail = ex.program(1, 3, x)
    assert tail.consts[0] is whole.consts[1]  # host[1] uploaded once
    assert whole.consts[2] is dev and tail.consts[1] is dev
    np.testing.assert_allclose(np.asarray(ex(0, 3, x)),
                               np.asarray(ex(0, 3, np.asarray(x))), rtol=1e-5, atol=1e-5)


def test_deployment_metrics_gauge_the_stage_executors():
    graph, _ = model_zoo.demo_mlp(d=32)
    d = deploy(DeploymentSpec(
        model="demo_mlp", microbatch=4,
        cluster=ClusterSpec(n_nodes=8, capacity_bytes=graph.total_param_bytes / 3,
                            seed=3)))
    for i in range(8):
        d.submit(jnp.ones((32,)) * 0.01 * i)
    d.drain()
    snap = d.metrics()["observability"]["metrics"]
    gauges = {g["name"]: g["value"] for g in snap["gauges"]}
    counters = {c["name"]: c["value"] for c in snap["counters"]}
    stages = len(d.control.pipeline.pods)
    assert gauges["stage_eager_calls"] == 0
    assert gauges["stage_traces"] == stages  # one batch shape
    assert gauges["stage_compiled_calls"] == stages * counters["microbatches_completed"]
