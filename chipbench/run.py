#!/usr/bin/env python3
"""Chip benchmark of the Reflex query engine: one cell, one run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program (``src/repro``) and
``BENCHMARK.json``, on a machine whose first JAX device is a TPU; anywhere
else it exits non-zero and prints no result. One run:

1. **Set-up** (``setup_s``, from process start): the configuration's tables
   are generated on the host from ``--seed`` by the plain reference,
   secret-shared with the program's ``SecretTable`` and served by
   ``ReflexClient.in_process`` with the configuration's deployment settings.
   A warm-up client, with a noise key of its own, answers each template of
   the mix once, so that every program whose shapes do not hang on noise is
   compiled or loaded from the cache.
2. **Window** (``--seconds``): a fresh client serves the traffic mix's
   closed loop under a Resizer noise key drawn from fresh entropy (printed;
   ``--noise-key`` replays one). A Resizer's trimmed size, and so every
   shape after it, is new on every query, as in a deployment: the programs
   those shapes need compile inside the window, and the run prints how many
   and for how long. The window ends with the first unit (a whole round of
   the mix) that completes after ``--seconds``. With ``--trace 1`` the
   window runs under the JAX profiler and the program's span tracer.
3. **Check**: every answer of the window against the plain reference's,
   and the noise of every trimmed size a Resizer revealed against the noise
   the configuration states (see ``check``).

The last line of standard output is the result as JSON; the compared
numbers and their limits are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import secrets  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HOME = Path(__file__).resolve().parent
ROOT = HOME.parent
sys.path.insert(0, str(HOME))

from bench import Benchmark, Cell  # noqa: E402
from loadgen import Mix, Request  # noqa: E402

TRACE_DIR = ROOT / ".chipbench" / "trace"
# The largest share by which the mean noise of a run's trimmed sizes may
# miss the mean that the configuration's noise gives (PERF.md, section 2).
NOISE_GAP_LIMIT = 0.5


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and the number of
    backend compiles, from its own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.compiles += event.endswith("backend_compile_duration")

    def mark(self):
        return (self.seconds, self.compiles)

    def since(self, mark):
        return (self.seconds - mark[0], self.compiles - mark[1])


def peak_device_bytes() -> int:
    """Peak bytes in use on the fullest local device since the process
    started; 0 where the backend keeps no such count (the host CPU)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    return int(max(peaks, default=0))


def derive_seeds(seed: int) -> Dict[str, int]:
    """Independent 31-bit streams of data and sharing from one ``--seed`` of
    any size."""
    import numpy as np

    data, share = np.random.SeedSequence(seed % 2**64).generate_state(2)
    return {"data": int(data), "share": int(share) >> 1}


# -----------------------------------------------------------------------------
# the deployment
# -----------------------------------------------------------------------------

def make_tables(cell: Cell, seeds: Dict[str, int]):
    """Plaintext from the reference, shared by the program; returns
    (shared tables, plaintext, catalog)."""
    import jax

    from repro.ops.table import SecretTable
    from repro.sql.catalog import Catalog

    cfg = cell.config
    plain = cell.reference.generate(
        n=int(cfg["rows"]), seed=seeds["data"], n_patients=int(cfg["demographics_rows"])
    )
    keys = jax.random.split(jax.random.PRNGKey(seeds["share"]), len(plain))
    tables = {name: SecretTable.from_plaintext(cols, k)
              for (name, cols), k in zip(plain.items(), keys)}
    jax.block_until_ready([t.valid.shares for t in tables.values()])
    catalog = Catalog.from_tables(
        tables, multiplicity=cell.reference.max_fanout(plain, cfg["fanout_key"])
    )
    return tables, plain, catalog


def service_kwargs(config: Dict) -> Dict:
    """The configuration's deployment settings as the service takes them."""
    from repro.core.noise import NoTrim, RevealNoise, TruncatedLaplace
    from repro.service.accountant import PrivacyAccountant

    r = config["resizer"]
    if r["noise"] == "truncated_laplace":
        noise = TruncatedLaplace(eps=r["eps"], delta=r["delta"], sensitivity=r["sensitivity"])
    elif r["noise"] == "none":
        noise = NoTrim()
    elif r["noise"] == "reveal":
        noise = RevealNoise()
    else:
        raise ValueError(f"unknown resizer noise {r['noise']!r}")
    a = config["accountant"]
    return {
        "noise": noise,
        "addition": r["addition"],
        "placement": config["placement"],
        "accountant": PrivacyAccountant(
            err=a["err"], confidence=a["confidence"], policy=a["policy"]
        ),
    }


# -----------------------------------------------------------------------------
# the closed loop
# -----------------------------------------------------------------------------

@dataclasses.dataclass
class Done:
    """One request's outcome: ``result`` is the program's QueryResult, or
    None when the query was refused."""

    request: Request
    latency_s: float
    result: object
    unit: int


def annotation(name: str):
    """A host span in the profiler's own trace (a no-op when none runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_unit(client, reqs: List[Request], index: int) -> List[Done]:
    """Serve one unit of requests, one at a time, each waiting for its
    answer."""
    from repro.errors import BudgetRefused

    out: List[Done] = []
    for r in reqs:
        t0 = time.perf_counter()
        try:
            with annotation(f"submit {r.template}"):
                res = client.submit(r.tenant, r.sql)
        except BudgetRefused:
            res = None
        out.append(Done(r, time.perf_counter() - t0, res, index))
    return out


# -----------------------------------------------------------------------------
# the check
# -----------------------------------------------------------------------------

def tlap_mean_eta(eps: float, delta: float, sensitivity: float, cap: float) -> float:
    """E[min(X, cap)] for X ~ Laplace(mu, b) truncated to [0, inf), with
    b = sensitivity / eps and mu = -b ln(2 delta): the mean number of fillers
    a Resizer keeps under parallel addition, where a draw is capped at the
    N - T fillers there are. Worked from the Laplace survival function, so
    that it owes nothing to the program's own noise code."""
    b = sensitivity / eps
    mu = -b * math.log(2.0 * delta)
    if cap <= 0:
        return 0.0
    if cap <= mu:
        area = cap - 0.5 * b * (math.exp((cap - mu) / b) - math.exp(-mu / b))
    else:
        area = (mu - 0.5 * b * (1.0 - math.exp(-mu / b))
                + 0.5 * b * (1.0 - math.exp(-(cap - mu) / b)))
    return area / (1.0 - delta)


def resize_inputs(plan) -> List[str]:
    """The intermediate under each Resize of ``plan``, in execution order
    (post-order, children left to right): ``"join"`` where a join is below
    it, else the one table it reads."""
    from repro.plan.nodes import Join, Resize, Scan

    def below(node):
        stack, tables, joined = [node], set(), False
        while stack:
            n = stack.pop()
            joined |= isinstance(n, Join)
            if isinstance(n, Scan):
                tables.add(n.table)
            stack.extend(n.children())
        if joined:
            return "join"
        (table,) = tables
        return table

    out: List[str] = []

    def walk(node):
        for c in node.children():
            walk(c)
        if isinstance(node, Resize):
            out.append(below(node.child))

    walk(plan)
    return out


def trims(result) -> List[tuple]:
    """(intermediate, N, S) of every Resize of one answered query, in
    execution order."""
    stats = [s for s in result.report.nodes if s.node.startswith("Resize")]
    inputs = resize_inputs(result.plan)
    if len(inputs) != len(stats):
        raise RuntimeError(f"plan has {len(inputs)} Resizes, report {len(stats)}")
    return [(i, s.n_in, s.n_out) for i, s in zip(inputs, stats)]


def check(config: Dict, reference, plain, done: List[Done]) -> Dict[str, Dict]:
    """The numbers compared, each with its limit (see PERF.md for how each
    limit was set), against what the configuration states:

    * ``wrong``: answers that differ from the reference's, or never came;
    * ``noise_gap``, where the deployment discloses noisy sizes: the share
      by which the fillers that the run's Resizers kept, S - T summed over
      every Resize, miss what the stated truncated Laplace noise keeps on
      average (``tlap_mean_eta``), T the reference's true size of each
      intermediate. Exact sizes read 1; too little noise reads near 1;
    * ``reveals``, where it discloses none (``"discloses": false``): the
      Resizes that trimmed."""
    templates = sorted({d.request.template for d in done})
    answers = {t: reference.answer(t, plain) for t in templates}
    sizes = {t: reference.true_sizes(t, plain) for t in templates}
    wrong, kept, expected, trimmed = 0, 0.0, 0.0, 0
    noise = config["resizer"]
    for d in done:
        res = d.result
        if res is None or not reference.check_rows(d.request.template, res.rows,
                                                  answers[d.request.template]):
            wrong += 1
            continue
        for inter, n, s in trims(res):
            trimmed += s < n
            if noise["noise"] == "truncated_laplace":
                t = sizes[d.request.template][inter]
                kept += s - t
                expected += tlap_mean_eta(noise["eps"], noise["delta"],
                                          noise["sensitivity"], n - t)
    out = {"wrong": {"value": wrong, "limit": 0}}
    if config["discloses"]:
        gap = abs(kept / expected - 1.0) if expected > 0 else 1.0
        out["noise_gap"] = {"value": gap, "limit": NOISE_GAP_LIMIT}
    else:
        out["reveals"] = {"value": trimmed, "limit": 0}
    return out


# -----------------------------------------------------------------------------
# one run
# -----------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: Cell
    setup_s: float
    window_s: float
    done: List[Done]
    compile_s: float  # JAX compile seconds inside the window
    compiles: int  # backend compiles inside the window
    spans: list = dataclasses.field(default_factory=list)  # obs.trace spans (traced runs)
    trace: Optional[object] = None  # trace_reduce.Reduced (traced runs)

    @property
    def answered(self) -> List[Done]:
        return [d for d in self.done if d.result is not None]


def describe_unit(done: List[Done]) -> None:
    """Per template: rows, trims (intermediate, N, S), join algorithms."""
    from repro.plan.nodes import Join, JoinSortMerge

    seen = set()
    for d in done:
        if d.result is None or d.request.template in seen:
            continue
        seen.add(d.request.template)
        joins, stack = [], [d.result.plan]
        while stack:
            node = stack.pop()
            if isinstance(node, Join):
                joins.append("sortmerge" if isinstance(node, JoinSortMerge) else "product")
            stack.extend(node.children())
        rows = len(next(iter(d.result.rows.values()), [])) if d.result.rows else 0
        log(f"template {d.request.template}: rows={rows} trims={trims(d.result)} joins={joins}")


def run_cell(bench: Benchmark, name: str, seed: int, seconds: float, trace: bool,
             noise_key: Optional[int] = None, service_override: Optional[Dict] = None,
             warm: bool = True) -> Dict:
    """One run of cell ``name``; returns the result line's object.
    ``service_override`` replaces keys of the configuration for the service
    alone (a control); the check holds the run to the configuration as it
    stands. ``warm=False`` skips the warm-up, for readings of the check that
    need no timing."""
    import jax

    from repro.obs.trace import Tracer
    from repro.runtime import ReflexClient

    cell = bench.cell(name)
    served = {**cell.config, **(service_override or {})}
    mix = Mix.from_file(cell.traffic_file)
    seeds = derive_seeds(seed)
    if noise_key is None:
        noise_key = secrets.randbits(31)
    warm_key = (noise_key + 1) % 2**31
    clock = CompileClock()
    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    log(f"seeds: data={seeds['data']} share={seeds['share']} "
        f"noise_key={noise_key} warm_key={warm_key}")

    tables, plain, catalog = make_tables(cell, seeds)
    log("tables: " + " ".join(f"{t}={v.n}" for t, v in tables.items())
        + f" fanout={cell.reference.max_fanout(plain, cell.config['fanout_key'])}")

    def make_client(key: int):
        return ReflexClient.in_process(
            tables, catalog=catalog, key=jax.random.PRNGKey(key), **service_kwargs(served),
        )

    if warm:
        mark, t0 = clock.mark(), time.perf_counter()
        with contextlib.closing(make_client(warm_key)) as warm_client:
            run_unit(warm_client, mix.unit(0), 0)
        comp_s, comp_n = clock.since(mark)
        log(f"warm-up: seconds={time.perf_counter() - t0:.3f} compiles={comp_n} "
            f"compile_s={comp_s:.3f}")
        gc.collect()

    client = make_client(noise_key)
    tracer = Tracer() if trace else None
    if trace:
        import trace_reduce

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    setup_s = time.perf_counter() - T_START
    mark = clock.mark()
    done: List[Done] = []
    t0 = time.perf_counter()
    with tracer if tracer is not None else contextlib.nullcontext():
        while True:
            idx = len({d.unit for d in done})
            done += run_unit(client, mix.unit(idx), idx)
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    comp_s, comp_n = clock.since(mark)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
    memory_peak = peak_device_bytes()
    if trace:
        reduced = trace_reduce.reduce_dir(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    acct = client.service.accountant
    log(f"window: seconds={window_s:.3f} units={len({d.unit for d in done})} queries={len(done)} "
        f"compiles={comp_n} compile_s={comp_s:.3f}")
    log("latencies_s: " + " ".join(f"{d.request.template}={d.latency_s:.3f}" for d in done))
    log(f"accountant: escalations={acct.escalation_count} refusals={acct.refusal_count}")
    log(f"memory: peak_bytes_in_use={memory_peak}")
    describe_unit(done)
    client.close()
    del client, tables
    gc.collect()

    checks = check(cell.config, cell.reference, plain, done)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    run = Run(cell=cell, setup_s=setup_s, window_s=window_s, done=done,
              compile_s=comp_s, compiles=comp_n,
              spans=tracer.spans if tracer is not None else [], trace=reduced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = bench.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    line = {
        "correct": correct,
        "attempted": len(done),
        "failed": checks["wrong"]["value"],
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        line["breakdown"] = {"device_ops": reduced.device_ops, "idle_gaps": reduced.idle_gaps}
    line["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']} limit {c['limit']}")
    return line


def use_compile_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path in this checkout,
    through the program's own switch, keeping every program, however quick
    its compile: the engine runs hundreds of programs that compile in under
    the program's 0.1 s floor, and each run is a new process, so without
    them every run compiles them again in set-up, and again in the window
    wherever fresh noise leads a sort into a padded size that the warm-up
    did not meet. Eviction stays off: it keys on an access-time file beside
    every entry, and one entry without it fails every later write."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--noise-key", type=int, default=None,
                    help="replay a run's printed noise_key (default: fresh entropy)")
    args = ap.parse_args(argv)

    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"run.py: needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s); nothing run")
        return 2
    line = run_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace),
                    noise_key=args.noise_key)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
