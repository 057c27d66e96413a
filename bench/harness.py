"""One run of one cell: set up, drive ``deploy()`` on the wall clock for the
window, check the answers against the reference, and print the result.

Set-up (counted in ``setup_s``, from process start to the first request due):
the compile cache, the weights made on the device from the seed, ``deploy()``,
and one pass through the pipeline at every batch size the cell's engine can
form, so that every shape the window uses is compiled before it opens.

The window drives the deployment only through its public surface, ``submit``
and ``step``, as a closed loop: each client submits its next request when its
last one is done.  A request is done when its answer is ready on the device;
the harness polls readiness between steps and never blocks on the device.

After the window closes the harness keeps stepping, without new requests,
until every request due in the window is done (a minute at most), reads the
device's peak memory, frees the deployment, and compares a seeded sample of
the answers, drawn over every answer of the run, with the reference
(``compare``, ``judge``).
"""

from __future__ import annotations

import gc
import json
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import traffic
from bench.manifest import Manifest
from bench.peaks import peaks_for

DRAIN_LIMIT_S = 60.0  # how long answers due in the window may come late
IDLE_SLEEP_S = 0.0002


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileClock:
    """Backend compiles and their seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


@dataclass
class Req:
    idx: int
    client: int
    due_s: float  # seconds from the window's start: the submit
    prompt: int
    request: object = None  # the engine's Request
    done_s: float | None = None  # answer ready on the device
    failed: bool = False
    answer: object = None  # a host copy of the answer, kept for the check


@dataclass
class Run:
    """What one run saw; the metric readers (``bench/metrics``) read it."""

    cell: str
    cfg: dict
    mix: dict
    seconds: float
    seed: int
    trace: bool
    peaks: dict
    flops_per_request: float
    setup_s: float = 0.0
    deploy_ms: float = 0.0
    reqs: list = field(default_factory=list)
    window_s: float = 0.0  # the measured window: ``seconds`` on to the batch that closes it
    compiles_in_window: int = 0
    compile_s_in_window: float = 0.0
    device: object = None  # devtrace.DeviceTrace of a traced run

    def due_in_window(self) -> list:
        return [r for r in self.reqs if r.due_s < self.seconds]


class Sample:
    """The answers compared with the reference: a uniform sample of ``k``
    over every answer of the run, drawn from the seed as the answers come
    (reservoir sampling, Vitter's algorithm R).  A kept answer is copied to
    the host and every device buffer is let go at once, so the sample holds
    no device memory: a chip that the model fills is left as the program
    would leave it."""

    def __init__(self, k: int, rng):
        self.k, self.rng, self.seen, self.kept = int(k), rng, 0, []

    def offer(self, r: Req) -> None:
        if len(self.kept) < self.k:
            self.kept.append(r)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j >= self.k:
                r.request.result = None
                self.seen += 1
                return
            self.kept[j].answer = None
            self.kept[j] = r
        r.answer = np.asarray(r.request.result)
        r.request.result = None
        self.seen += 1


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def check_devices(chips: int):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from None
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX runs on {devices[0].platform})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def configure_cache() -> str:
    """The program's compile-cache directory, with thresholds low enough
    that the eager ops' small programs are kept as well."""
    import jax
    from repro.launch.compile_cache import configure_compile_cache

    where = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def make_prompts(words: list[int], pool: int, seq: int, vocab: int):
    """``pool`` prompts of ``seq`` token ids, drawn on the device in one
    call; returns the (pool, seq) array and its rows."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
    ids = jax.jit(lambda k: jax.random.randint(k, (pool, seq), 0, vocab, jnp.int32))(key)
    return ids, [ids[i] for i in range(pool)]


def warm_up(d, rows, sizes) -> None:
    """One pass through the whole pipeline at each batch size."""
    import jax

    for b in sizes:
        reqs = [d.submit(rows[i % len(rows)]) for i in range(b)]
        d.drain()
        if len(d.loop.failed):
            raise RuntimeError(f"warm-up at batch {b}: {len(d.loop.failed)} request(s) failed")
        jax.block_until_ready([r.result for r in reqs])


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class Driver:
    """Drives one deployment on the wall clock; times are seconds from the
    window's start (``t0``)."""

    def __init__(self, d, run: Run, rows, sample: Sample, annotate):
        self.d, self.run, self.rows, self.sample = d, run, rows, sample
        self.annotate = annotate
        self.by_id: dict[int, Req] = {}
        self.awaiting: list[list[Req]] = []  # batches done in the engine, answers not all ready
        self.failed_seen = 0
        self.new_failures: list[Req] = []
        self.t0 = 0.0

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def submit(self, due_s: float, client: int) -> Req:
        idx = len(self.run.reqs)
        r = Req(idx, client, due_s, idx % len(self.rows))
        with self.annotate("bench.submit"):
            r.request = self.d.submit(self.rows[r.prompt])
        self.by_id[r.request.req_id] = r
        self.run.reqs.append(r)
        return r

    def step(self) -> bool:
        d = self.d
        if not (d.loop.backlog or d.pending):
            return False
        with self.annotate("bench.step"):
            done = d.step()
        group = [self.by_id[req.req_id] for req in done if req.req_id in self.by_id]
        if group:
            self.awaiting.append(group)
        failed = d.loop.failed
        while self.failed_seen < len(failed):
            r = self.by_id.get(failed[self.failed_seen].req_id)
            self.failed_seen += 1
            if r is not None:
                r.failed, r.done_s = True, self.now()
                self.new_failures.append(r)
        return True

    def poll(self) -> list[Req]:
        """Answers that became ready on the device since the last poll.

        The requests one ``step`` completed are one batch, and the engine
        hands each its row of the batch's output as a slice of its own.
        A batch is looked at as a whole: its answers count as ready when
        every slice is, all at that time.  Single slices seen ready one
        poll apart would send a closed loop's callers back at different
        times, split its next batch, and change the batch sizes, and with
        them the work, from run to run."""
        if not self.awaiting:
            return []
        ready = []
        with self.annotate("bench.poll"):
            t, still = self.now(), []
            for group in self.awaiting:
                if not all(r.request.result.is_ready() for r in group):
                    still.append(group)
                    continue
                for r in group:
                    r.done_s = t
                    self.sample.offer(r)
                ready.extend(group)
            self.awaiting = still
        return ready

    def drive(self, seconds: float) -> None:
        """Run the window.  Each client sends its next request when its last
        one is answered, or at once when it failed.  The window closes at
        the first batch answered at or after ``seconds``: answers come eight
        or so at a time, seconds apart, and a window that closed between two
        batches would count whole batches in or out by where its end fell."""
        run = self.run
        self.t0 = time.perf_counter()
        for c in range(int(run.mix["clients"])):
            self.submit(0.0, c)
        with self.annotate("bench.window"):
            while True:
                if self.now() >= seconds + DRAIN_LIMIT_S:  # no batch came to close it
                    run.window_s = self.now()
                    break
                busy = self.step()
                ready = self.poll()
                if ready and ready[0].done_s >= seconds:
                    run.window_s = ready[0].done_s
                    break
                for r in ready:
                    self.submit(r.done_s, r.client)
                failures, self.new_failures = self.new_failures, []
                for r in failures:
                    if self.now() < seconds:
                        self.submit(self.now(), r.client)
                if not (busy or ready):
                    time.sleep(IDLE_SLEEP_S)

    def wait_for_due(self, seconds: float) -> None:
        """Keep stepping, with no new requests, until every request due in
        the window is done or has failed, or ``DRAIN_LIMIT_S`` has passed."""
        due = [r for r in self.run.reqs if r.due_s < seconds]
        with self.annotate("bench.drain"):
            while self.now() < seconds + DRAIN_LIMIT_S:
                if all(r.done_s is not None for r in due):
                    break
                busy = self.step()
                busy |= bool(self.poll())
                if not busy:
                    time.sleep(IDLE_SLEEP_S)


# ---------------------------------------------------------------------------
# the comparison with the reference
# ---------------------------------------------------------------------------

def _rel_l2(got, want) -> float:
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    return float(jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel()))


def compare(ref, cfg: dict, weights, ids, kept: list[Req], block: int,
            control: bool = False) -> tuple[list[float], list[float]]:
    """Relative L2 error of each kept answer against the float32 reference,
    computed ``block`` prompts at a time.  With ``control``, also that of
    the control's answer to each of the same prompts: the reference with
    every projection in float8_e4m3 (``ref.dot_fp8``), one precision step
    below the configuration's bf16, put in the program's place."""
    import jax.numpy as jnp

    errs, ctrl = [], []
    for i in range(0, len(kept), block):
        part = kept[i:i + block]
        tokens = ids[jnp.asarray([r.prompt for r in part])]
        want = ref.forward(cfg, weights, tokens)
        lower = ref.forward(cfg, weights, tokens, ref.dot_fp8) if control else None
        for j, r in enumerate(part):
            errs.append(_rel_l2(r.answer, want[j]))
            if control:
                ctrl.append(_rel_l2(lower[j], want[j]))
    return errs, ctrl


def judge(errs: list[float], n_failed: int, limit) -> tuple[bool, dict]:
    """The verdict of a run: every request due in the window answered, at
    least one answer compared, and the largest relative error within the
    configuration's limit.  Returns ``correct`` and each number compared
    beside its limit."""
    worst = max(errs) if errs else None
    checks = {
        "rel_err_max": {"value": worst, "limit": limit},
        "compared": {"value": len(errs), "limit": 1},
        "failed": {"value": n_failed, "limit": 0},
    }
    correct = limit is not None and worst is not None and worst <= limit and n_failed == 0
    return correct, checks


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(manifest: Manifest, name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float | None = None, require_chip: bool = True,
             executor_hook=None, control: bool = False, out_dir: Path | None = None,
             log=print) -> dict:
    """Run one cell; returns the result object the run prints last.

    ``require_chip=False`` skips the look for a chip (tests on the CPU run
    the kernels in interpret mode) and ``executor_hook`` wraps the deployed
    executor (tests break the timed path with it).  ``control=True`` judges
    the control's answers in place of the program's (``bench/control.py``;
    the benchmark's own runs never set it) and logs the program's checks."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell = manifest.cell(name)
    cfg = manifest.config_json(cell)
    mix = traffic.validate(manifest.traffic(cell))
    ref, builder = manifest.reference(cell), manifest.builder(cell)

    import jax

    if require_chip:
        devices = check_devices(cell.chips)
        configure_cache()
    else:
        devices = jax.devices()
    kind = devices[0].device_kind
    peaks = peaks_for(kind if require_chip else "TPU v5 lite")
    clock = CompileClock()
    from repro.api import ClusterSpec, DeploymentSpec, deploy

    words = traffic.seed_words(seed, 4)
    seq = int(mix["prompt_len"])
    weights = ref.init_weights(cfg, words[:2])
    ids, rows = make_prompts(words[2:], int(mix["prompt_pool"]), seq, int(cfg["vocab_size"]))
    graph, efv = builder.build(cfg, weights, ref, seq=seq, use_pallas=True,
                               interpret=not require_chip)
    if executor_hook is not None:
        efv = executor_hook(efv)
    dep = mix["deployment"]
    spec = DeploymentSpec(
        model=graph, executor_for_version=efv,
        cluster=ClusterSpec(n_nodes=int(dep["nodes"]),
                            capacity_bytes=graph.total_param_bytes * float(dep["capacity_frac"]),
                            seed=int(dep["cluster_seed"])),
        codec=dep["codec"], microbatch=int(dep["microbatch"]),
        max_batch=dep.get("max_batch"), queue_depth=int(dep.get("queue_depth", 2)),
        seed=int(dep.get("spec_seed", 0)), use_pallas=True, interpret=not require_chip,
    )
    run = Run(cell=name, cfg=cfg, mix=mix, seconds=float(seconds), seed=seed,
              trace=trace, peaks=peaks,
              flops_per_request=ref.flops_per_request(cfg, seq))
    sample = Sample(mix["check"]["sample"], traffic.rng(seed, "check"))
    trace_dir = None
    with tempfile.TemporaryDirectory(prefix="bench-store-") as store:
        t = time.perf_counter()
        d = deploy(spec, store_root=store)
        run.deploy_ms = (time.perf_counter() - t) * 1e3
        stages = [(p.node_id, p.partition.start, p.partition.stop) for p in d.control.pipeline.pods]
        log(f"plan: {len(stages)} stages (node, first layer, end) {stages}; "
            f"link codecs {list(d.plan.codecs)}", file=sys.stderr)
        warm_up(d, rows, traffic.warm_batch_sizes(mix))
        if trace:
            trace_dir = Path(out_dir or "results") / f"trace-{name}-{seed}-{int(time.time())}"
            jax.profiler.start_trace(str(trace_dir), profiler_options=_profile_options())
        annotate = jax.profiler.TraceAnnotation
        driver = Driver(d, run, rows, sample, annotate)
        c0, s0 = clock.count, clock.seconds
        run.setup_s = time.perf_counter() - t_process
        driver.drive(run.seconds)
        run.compiles_in_window = clock.count - c0
        run.compile_s_in_window = clock.seconds - s0
        if trace:
            jax.profiler.stop_trace()
        driver.wait_for_due(run.seconds)
        log(f"compiles or compile-cache loads in the window: {run.compiles_in_window} "
            f"({run.compile_s_in_window:.6f} s)", file=sys.stderr)
        done = sorted(r.done_s for r in run.reqs if r.done_s is not None and not r.failed)
        log(f"answers: {len(done)} of {len(run.reqs)} requests; "
            f"ready at {[round(t, 3) for t in done]}", file=sys.stderr)
        peak = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for dv in devices[:cell.chips])
        kept = sorted(sample.kept, key=lambda r: r.idx)
        del d, driver
        gc.collect()
    if trace:
        from bench import devtrace

        events = devtrace.events_from_xplane(devtrace.find_xplane(trace_dir), cell.chips)
        run.device = devtrace.DeviceTrace.from_events(events)

    t = time.perf_counter()
    errs, ctrl = compare(ref, cfg, weights, ids, kept, int(cfg.get("ref_block", 1)), control)
    log(f"reference check: {len(errs)} answers of {sample.seen} in "
        f"{time.perf_counter() - t:.3f} s", file=sys.stderr)
    due = run.due_in_window()
    n_failed = sum(1 for r in due if r.failed or r.done_s is None)
    correct, checks = judge(errs, n_failed, cfg["check_limit"])
    if control:
        for k, v in checks.items():
            log(f"program check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
        correct, checks = judge(ctrl, n_failed, cfg["check_limit"])

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = manifest.metric_reader(m).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": len(due), "failed": n_failed,
              "metrics": metrics, "device": device}
    if run.device is not None:
        device["busy_s"] = run.device.busy_s
        device["window_s"] = run.device.window_s
        result["breakdown"] = {"device_ops": run.device.top_programs(10),
                               "idle_gaps": run.device.idle_gaps(10)}
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    return result


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the Python tracer records every call
    opts.host_tracer_level = 1  # the harness's own annotations
    return opts


def dumps(result: dict) -> str:
    return json.dumps(result, allow_nan=False)
