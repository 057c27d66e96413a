"""Whole runs of tiny cells on the CPU (kernels in interpret mode): the
configurations' builders served through ``deploy()`` agree with their
float32 references, a broken timed path makes ``correct`` false, and the
control (the reference in float8 put in the program's place) is judged not
correct by the harness's own rule."""

from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench.harness import run_cell
from bench.manifest import Manifest
from perfbench_tiny import TINY_CELLS, tiny_bench

SEED = 2 ** 40 + 7  # wider than 32 bits: a run takes a seed of any size
SECONDS = 1.0


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny")
    return Manifest.load(tiny_bench(root), root / "bench")


def run(manifest, cell, hook=None, control=False):
    return run_cell(manifest, cell, SEED, SECONDS, False, require_chip=False,
                    executor_hook=hook, control=control, log=lambda *a, **k: None)


@pytest.fixture(scope="module")
def sound(manifest):
    return {cell: run(manifest, cell) for cell in TINY_CELLS}


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_sound_run_matches_the_reference(manifest, sound, cell):
    res = sound[cell]
    assert res["correct"], res["checks"]
    assert res["checks"]["rel_err_max"]["value"] < 0.05
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"] for m in manifest.cell(cell).end_to_end}
    assert set(res["metrics"]) == want
    assert list(res)[-1] == "checks"  # the compared numbers come last


def _wrap(efv, change):
    """An executor_for_version whose executor applies ``change(start,
    stop, y)`` to each stage's output."""
    def efv2(version):
        ex = efv(version)

        def executor(start, stop, x):
            return change(start, stop, ex(start, stop, x))

        executor.fused_codecs = ex.fused_codecs
        return executor
    return efv2


def _answer_altered(n_layers):
    def change(start, stop, y):
        if stop != n_layers:
            return y
        half = y.shape[-1] // 2
        return y.at[..., :half].multiply(-1.0)  # an answer altered where produced
    return change


def _rows_swapped(n_layers):
    def change(start, stop, y):
        return jnp.roll(y, 1, axis=0) if stop == n_layers else y
    return change


def _layer_skipped(start_layer):
    def efv_change(efv):
        def efv2(version):
            ex = efv(version)

            def executor(start, stop, x):
                if start <= start_layer < stop:  # run the stage without that layer
                    x = ex(start, start_layer, x) if start < start_layer else x
                    return ex(start_layer + 1, stop, x) if start_layer + 1 < stop else x
                return ex(start, stop, x)

            executor.fused_codecs = ex.fused_codecs
            return executor
        return efv2
    return efv_change


FAULTS = {
    "answer_altered": lambda n: (lambda efv: _wrap(efv, _answer_altered(n))),
    "rows_swapped": lambda n: (lambda efv: _wrap(efv, _rows_swapped(n))),
    "layer_skipped": lambda n: _layer_skipped(1),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(manifest, fault):
    cell = "tiny-mamba.closed"
    n_layers = 2 + manifest.config_json(manifest.cell(cell))["n_layer"]
    res = run(manifest, cell, hook=FAULTS[fault](n_layers))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_control_is_not_correct(manifest, sound, cell):
    program = sound[cell]["checks"]["rel_err_max"]["value"]
    res = run(manifest, cell, control=True)
    assert res["correct"] is False, res["checks"]
    assert res["failed"] == 0 and res["checks"]["compared"]["value"] >= 3
    control = res["checks"]["rel_err_max"]
    assert control["value"] > control["limit"] > 2 * program, (control, program)
