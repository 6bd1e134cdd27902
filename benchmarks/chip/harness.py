"""One run of one cell: build, warm up, measure, check.

Everything that belongs to one configuration, traffic mix, cell or metric
is found by name:

* ``BENCHMARK.json`` names the cell, its configuration file and its mix;
* ``configs/<config>.json`` gives the sizes, and its ``architecture`` names
  ``reference/<architecture>.py`` (the plain reference, which also makes the
  weights) and ``arch/<architecture>.py`` (the program's side);
* ``traffic/<mix>.json`` gives the parameters ``traffic.py`` generates from;
* ``cells/<workload>.json`` gives the engine's settings, the depth of the
  backlog and the limits of the correctness check;
* ``metrics/<metric>.py`` reads one metric from the run (``read(run)``).

The run drives the program only through ``ServingEngine.submit`` and
``ServingEngine.step``, and reads its public state and counters after each
step.  Host spans (``bench.step``, ``bench.submit``, ``bench.harvest``) go
into the profiler's trace.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parents[1]
KERNELS = ("PagedAttn", "PrefillAttn")
WARM_NEW_TOKENS = (2, 3, 5, 9)  # emit 1, 2, 4 and 8 tokens after prefill


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    params: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @classmethod
    def load(cls, workload: str, root: pathlib.Path = CHECKOUT) -> "Cell":
        bench = json.loads((root / "BENCHMARK.json").read_text())
        entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
        if entry is None:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
        config = json.loads((root / conf["file"]).read_text())
        config.setdefault("name", conf["name"])
        mix = json.loads((BENCH_DIR / "traffic" / f"{entry['traffic']}.json").read_text())
        params = json.loads((BENCH_DIR / "cells" / f"{workload}.json").read_text())
        mine = lambda m: "workloads" not in m or workload in m["workloads"]
        return cls(workload, entry["chips"], config, mix, params,
                   [m for m in bench["end_to_end"] if mine(m)],
                   [m for m in bench["per_layer"] if mine(m)])


class Tracked:
    """What the harness knows of one request it submitted."""

    __slots__ = ("spec", "req", "first", "nout", "done_at")

    def __init__(self, spec, req):
        self.spec, self.req = spec, req
        self.first = self.done_at = None
        self.nout = 0


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    dispatched: bool
    emitted: int
    prefill: list  # (uid, start, n) chunks run by this step
    decode: list  # (uid, first position, ticks) per slot
    pages_in_use: int


class Run:
    """One engine under one cell's traffic."""

    def __init__(self, cell: Cell, seed: int):
        import jax

        from repro.serving import ServeConfig, ServingEngine

        self.cell, self.seed = cell, seed
        arch = cell.config["architecture"]
        self.ref = _load_module(BENCH_DIR / "reference" / f"{arch}.py", f"bench_reference_{arch}")
        self.arch = _load_module(BENCH_DIR / "arch" / f"{arch}.py", f"bench_arch_{arch}")
        self.dims = self.ref.dims(cell.config)
        self.mcfg = self.arch.model_config(cell.config, self.dims)
        p = cell.params
        self.scfg = ServeConfig(
            slots=p["slots"], max_len=p["max_len"], page_size=p["page_size"],
            num_blocks=p.get("num_blocks"), prefill_chunk=p["prefill_chunk"],
            sync_every=p["sync_every"], temperature=0.0, eos_id=-1, seed=0,
        )
        params = self.arch.program_params(self.ref.make_weights(cell.config, seed), self.mcfg)
        self.engine = ServingEngine(self.mcfg, params, self.scfg)
        del params
        jax.block_until_ready(self.engine.cache)
        self.tracked: List[Tracked] = []
        self.live: List[Tracked] = []
        self.records: List[StepRecord] = []
        self.clock = time.monotonic

    # -- driving the engine ---------------------------------------------
    def submit(self, spec) -> Tracked:
        import jax

        with jax.profiler.TraceAnnotation("bench.submit"):
            req = self.engine.submit(spec.prompt.tolist(), max_new_tokens=spec.max_new)
        t = Tracked(spec, req)
        self.tracked.append(t)
        self.live.append(t)
        return t

    def _snapshot(self):
        e = self.engine
        return [
            None if r is None else (r, int(e.pos[s]), e.slot_state[s], r.preemptions, len(r.output))
            for s, r in enumerate(e.slot_req)
        ]

    def step(self) -> StepRecord:
        import jax

        e = self.engine
        before = self._snapshot()
        dispatches = e.dispatches
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("bench.step"):
            e.step()
        t1 = self.clock()
        with jax.profiler.TraceAnnotation("bench.harvest"):
            rec = self._harvest(before, t0, t1, e.dispatches > dispatches)
        self.records.append(rec)
        return rec

    def _harvest(self, before, t0: float, t1: float, dispatched: bool) -> StepRecord:
        e = self.engine
        prefill, decode = [], []
        for s, b in enumerate(before):
            r1 = e.slot_req[s]
            p1 = int(e.pos[s])
            same = b is not None and r1 is b[0] and r1.preemptions == b[3]
            if b is not None and not same:
                r0, p0, state, pre, nout = b
                if r0.done and r0.preemptions == pre:  # ended in this step
                    k = len(r0.output) - nout
                    if state == "gen" and k:
                        decode.append((r0.uid, p0, k))
                    elif state == "prefill" and k:
                        prefill.append((r0.uid, p0, len(r0.prompt) + nout - p0))
            if same:
                r0, p0, state, _, nout = b
                if state == "prefill" and p1 > p0:
                    prefill.append((r1.uid, p0, p1 - p0))
                elif state == "gen" and len(r1.output) > nout:
                    decode.append((r1.uid, p0, len(r1.output) - nout))
            elif r1 is not None and p1 > r1.cached_tokens:  # admitted now
                prefill.append((r1.uid, r1.cached_tokens, p1 - r1.cached_tokens))
        emitted = 0
        still = []
        for t in self.live:
            r = t.req
            n = len(r.output)
            if n > t.nout:
                emitted += n - t.nout
                if t.first is None:
                    t.first = t1
                t.nout = n
            if r.done:
                t.done_at = t1
            else:
                still.append(t)
        self.live = still
        pool = e.pool
        return StepRecord(t0, t1, dispatched, emitted, prefill, decode,
                          pool.in_use if pool is not None else 0)

    def busy(self) -> bool:
        e = self.engine
        return bool(e.queue) or any(r is not None for r in e.slot_req)

    # -- phases ----------------------------------------------------------
    def warm_programs(self, width: int) -> None:
        """Compile every program the cell's traffic can dispatch: the prefill
        step, the per-tick decode step beside a prefill, and the decode
        windows of 8, 4, 2 and 1 ticks (a window shrinks to the largest
        power of two its slots can use)."""
        rng = np.random.default_rng(0)
        prompt = lambda: rng.integers(0, self.dims.vocab, size=width, dtype=np.int32).tolist()
        e = self.engine
        for n in WARM_NEW_TOKENS:
            e.submit(prompt(), max_new_tokens=n)
            while self.busy():
                e.step()
        e.submit(prompt(), max_new_tokens=3)
        e.step()
        e.submit(prompt(), max_new_tokens=2)
        while self.busy():
            e.step()

    # -- traffic ---------------------------------------------------------
    def start_traffic(self, specs) -> None:
        self._pending = iter(specs)
        self._next = next(self._pending, None)

    def top_up(self, depth: int) -> None:
        """Keep ``depth`` requests waiting."""
        while len(self.engine.queue) < depth and self._next is not None:
            self.submit(self._next)
            self._next = next(self._pending, None)


class CompileCounter:
    """Counts compilation events JAX reports while ``armed``."""

    def __init__(self):
        import jax

        self.armed = False
        self.events: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and ("compile" in event or "compilation_cache" in event):
            self.events[event] = self.events.get(event, 0) + 1


class GcPauses:
    """Python's garbage collections while ``armed``: how many, of which
    generation, and how long the process stood still for them."""

    def __init__(self):
        self.armed = False
        self.pauses: List[tuple] = []  # (generation, seconds)
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            if self.armed:
                self.pauses.append((info["generation"], time.perf_counter() - self._t0))
            self._t0 = None

    def summary(self) -> dict:
        secs = [s for _, s in self.pauses]
        return {"count": len(secs), "gen2": sum(1 for g, _ in self.pauses if g == 2),
                "total_s": sum(secs), "max_s": max(secs, default=0.0)}

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def correctness(run_cell: Cell, seed: int, finished: List[Tracked], control: bool) -> dict:
    """Reference check of a sample of the requests the window finished:
    the longest, then others drawn from the seed, until at least 3 requests
    and 512 served tokens (at most 8 requests).  For each served token, the
    gap by which the reference's logit of it lies below the reference's
    best; with ``control``, also the gap of the token that the control (the
    reference with float8 operands) puts first at the same positions, and
    of an int8 and a bfloat16 witness."""
    import jax.numpy as jnp

    arch = run_cell.config["architecture"]
    ref = _load_module(BENCH_DIR / "reference" / f"{arch}.py", f"bench_reference_{arch}")
    d = ref.dims(run_cell.config)
    s_pad = run_cell.params["max_len"]
    done = sorted((t for t in finished if t.req.status == "completed"), key=lambda t: t.req.uid)
    if not done:
        return {"sampled": 0, "served_tokens": 0, "max_gap": None}
    rng = np.random.default_rng([seed, 1])
    longest = max(done, key=lambda t: (len(t.req.prompt) + len(t.req.output), -t.req.uid))
    rest = [t for t in done if t is not longest]
    order = [longest] + [rest[i] for i in rng.permutation(len(rest))]
    sample, tokens = [], 0
    for t in order:
        if len(sample) >= 3 and tokens >= 512 or len(sample) >= 8:
            break
        sample.append(t)
        tokens += len(t.req.output)
    weights = ref.make_weights(run_cell.config, seed)
    out = {"sampled": len(sample), "served_tokens": tokens, "max_gap": 0.0}
    if control:
        out.update(int8_gap=0.0, fp8_gap=0.0, bf16_gap=0.0)
    for t in sample:
        r = t.req
        seq = np.zeros((s_pad,), np.int32)
        full = list(r.prompt) + list(r.output[:-1])
        seq[: len(full)] = full
        rows = slice(len(r.prompt) - 1, len(full))
        served = jnp.asarray(np.asarray(r.output, np.int32))
        h = ref.hidden(weights, jnp.asarray(seq), d, "float32")[rows]
        g, _ = ref.gaps(weights, d, h, served)
        out["max_gap"] = max(out["max_gap"], float(jnp.max(g)))
        if control:
            for key, prec in (("int8_gap", "int8"), ("fp8_gap", "fp8"), ("bf16_gap", "bfloat16")):
                other = ref.hidden(weights, jnp.asarray(seq), d, prec)[rows]
                _, c = ref.gaps(weights, d, h, served, other, prec)
                out[key] = max(out[key], float(jnp.max(c)))
    return out


@dataclasses.dataclass
class View:
    """What a metric reader sees of one finished run."""

    cell: Cell
    dims: object
    peak: dict
    setup_s: float
    window_s: float
    steps: List[StepRecord]  # the window's steps
    traced_steps: List[StepRecord]  # the steps the profiler traced
    trace: Optional[dict]  # trace.reduce() of the traced window, or None
    requests: List[Tracked]  # ended in the window
    counters: Dict[str, float]  # engine counters over the window
    pool_blocks: int


def _device_info(devices) -> dict:
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


COUNTERS = ("dispatches", "preemptions", "decode_windows", "window_fallbacks", "table_uploads",
            "guard_failures", "poisoned_rows")


def measure(cell: Cell, seed: int, seconds: float, traced: bool, *, t_start: float,
            control: bool = False, peak: Optional[dict] = None,
            dump_trace: Optional[str] = None):
    """One run: returns ``(result, checks, info)``; ``result`` is the
    result line's object without its ``checks``.  With ``traced`` the
    profiler records the whole window."""
    import jax

    import devtrace as tr
    import traffic

    devices = jax.devices()[: cell.chips]
    counter = CompileCounter()
    pauses = GcPauses()
    run = Run(cell, seed)
    p = cell.params
    run.warm_programs(p["prefill_chunk"])
    run.start_traffic(traffic.generate(cell.mix, seed, run.dims.vocab, p["requests"]))
    depth = p["queue_depth"]
    # the window opens once every slot has served a first token
    while sum(1 for t in run.tracked if t.first is not None) < p["slots"]:
        run.top_up(depth)
        run.step()
    e = run.engine
    jax.block_until_ready(e.cache)

    # ---- the measured window -------------------------------------------
    counter.armed = pauses.armed = True
    before = {k: getattr(e, k) for k in COUNTERS}
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    t0 = run.clock()
    setup_s = t0 - t_start
    t_end = t0 + seconds
    if traced:
        jax.profiler.start_trace(tdir)
    n_rec = len(run.records)
    while run.clock() < t_end:
        run.top_up(depth)
        run.step()
    t_close = run.clock()
    if traced:
        jax.profiler.stop_trace()
    counter.armed = pauses.armed = False
    pauses.close()
    steps = run.records[n_rec:]
    counters = {k: getattr(e, k) - before[k] for k in COUNTERS}
    window_s = t_close - t0
    requests = [t for t in run.tracked if t.done_at is not None and t0 < t.done_at <= t_close]
    device = _device_info(devices)
    pool_blocks = e.pool.num_blocks if e.pool is not None else 0
    longest = sorted(steps, key=lambda r: r.t0 - r.t1)[:3]
    info = {
        "window_s": window_s, "steps": len(steps), "counters": counters,
        "compiles_in_window": dict(counter.events), "gc_in_window": pauses.summary(),
        "longest_steps_s": [[r.t1 - r.t0, len(r.prefill), max((k for _, _, k in r.decode), default=0)]
                            for r in longest],
        "requests": len(requests), "pool_blocks": pool_blocks,
    }

    # ---- free the program's state before the reference runs ------------
    run.engine = None
    del e
    gc.collect()

    reduced = None
    traced_steps: List[StepRecord] = []
    if traced:
        raw = tr.load(tr.find_xplane(tdir), KERNELS)
        shutil.rmtree(tdir, ignore_errors=True)
        if dump_trace:
            tr.to_json(raw, dump_trace)
        reduced = tr.reduce(raw)
        if reduced:
            # the steps whose spans the trace kept (all, unless its buffers filled)
            traced_steps = steps[: reduced["steps"]]
            info["trace"] = {k: reduced[k] for k in ("window_s", "busy_s", "idle_share", "steps",
                                                     "gap_count", "longest_gap_s", "program_s")}
    view = View(cell, run.dims, peak, setup_s, window_s, steps, traced_steps, reduced, requests,
                counters, pool_blocks)

    metrics = {}
    wanted = cell.per_layer if traced else cell.end_to_end
    for m in wanted:
        reader = _load_module(BENCH_DIR / "metrics" / f"{m['name']}.py", "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        info.setdefault("metric_notes", {})[m["name"]] = getattr(reader, "note", lambda v: None)(view)

    # ---- correctness ----------------------------------------------------
    failed = [t for t in requests
              if t.req.status != "completed" or len(t.req.output) != t.spec.max_new]
    check = correctness(cell, seed, requests, control)
    limits = p["limits"]
    checks = [
        ("max_logit_gap", check["max_gap"], limits["max_logit_gap"]),
        ("failed_requests", len(failed), 0),
        ("served_tokens_checked", check["served_tokens"], limits["min_served_tokens"]),
    ]
    correct = (check["max_gap"] is not None and check["max_gap"] <= limits["max_logit_gap"]
               and not failed and check["served_tokens"] >= limits["min_served_tokens"])
    info["check"] = check
    if traced:
        device["busy_s"] = reduced["busy_s"] if reduced else 0.0
        device["window_s"] = reduced["window_s"] if reduced else 0.0
    result = {"correct": bool(correct), "attempted": len(requests), "failed": len(failed),
              "metrics": metrics, "device": device}
    if traced and reduced:
        result["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    return result, checks, info
