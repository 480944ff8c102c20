"""One run of one cell: set-up, a measured window, the check, the metrics.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

- the configuration: the JSON file its entry names (``bench/configs/``):
  geometry, ``IndexConfig``, ``SearchParams``, ``ServeParams``,
  ``UpdateParams``, the guarantees and the limits of the check;
- the traffic mix: ``bench/traffic/<traffic>.json``, read by the one
  generator in ``traffic.py``;
- each metric: ``bench/metrics/<name>.py``, a reader with
  ``read(run: RunRecord) -> float | None``.  A reader that finds nothing
  returns None and the metric is left out of the line.

The run drives ``repro.serving.ThroughputEngine`` through its public
``submit`` / ``submit_upsert`` / ``submit_delete`` / ``pump`` calls from one
thread, and notes after every ``pump`` what it saw: batches dispatched
(``stats["batches"]``), batches completed (``stats["batch_records"]``,
in dispatch order; requests map to them in submission order, as the queue
is first-in first-out) and mutation tickets done.  A request's latency
runs from the time it was due to the first moment the client loop sees it
done.  A mutation ticket seen done after ``pump`` returned is visible to
every batch dispatched later and to none dispatched earlier: the engine
applies mutations only after draining every batch in flight.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import data as bdata
from bench import reference as ref
from bench import trace as btrace
from bench import traffic as btraffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
ACCELERATORS = ("tpu", "gpu")
MAX_DRAIN_S = 60.0       # an answer later than this past the window is lost


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


# ---------------------------------------------------------------------------
# the files, by name
# ---------------------------------------------------------------------------

def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(spec: dict, workload: str):
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def load_config(root: Path, entry: dict) -> dict:
    return json.loads((root / entry["file"]).read_text())


def load_traffic(root: Path, name: str) -> dict:
    return btraffic.load_mix(json.loads(
        (root / "bench" / "traffic" / f"{name}.json").read_text()))


def load_reader(root: Path, metric: str) -> Callable:
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a run of this cell reports: the end-to-end ones with
    ``--trace 0``, the per-layer ones with ``--trace 1``."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def load_peaks(root: Path, kind: str) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# what a run hands to the metric readers
# ---------------------------------------------------------------------------

@dataclass
class RunRecord:
    """What a metric reader reads.  Times are seconds from the window's
    start on the host clock."""
    loop: str                       # "closed" | "open"
    window_s: float
    setup_s: float
    due: np.ndarray                 # per request of the window
    t_dispatch: np.ndarray          # seen dispatched
    t_done: np.ndarray              # seen done (nan: never)
    completed_in_window: int        # requests seen done inside the window
    drain_limit_s: float            # when the run stopped waiting for answers
    batches: List[dict]             # engine batch records of the window
    checks: Dict[str, dict]
    trace: Optional[btrace.TraceSummary] = None
    counters: Optional[Dict[str, float]] = None    # per-query search counts
    shapes: Dict[str, int] = field(default_factory=dict)
    peaks: Optional[dict] = None


# ---------------------------------------------------------------------------
# the client loop
# ---------------------------------------------------------------------------

class Client:
    """Drives one engine from one thread and notes what it sees."""

    def __init__(self, eng, queries: np.ndarray, clock=time.perf_counter):
        self.eng = eng
        self.queries = queries
        self.clock = clock
        self.reqs: list = []
        self.qrow: List[int] = []
        self.due: List[float] = []
        self.t_sub: List[float] = []
        self.batch_of: List[int] = []
        self.t_done: List[float] = []
        self.t_disp: Dict[int, float] = {}
        self.n_disp = eng.stats["batches"]
        self.n_rec = len(eng.stats["batch_records"])
        self.tickets: deque = deque()
        self.acked: List[tuple] = []       # (ticket, epoch)
        self.failed_tickets = 0
        # bytes the device holds, read at each batch seen done while set
        self.mem_probe: Optional[Callable[[], int]] = None
        self.mem_peak = 0

    @property
    def outstanding(self) -> int:
        return len(self.reqs) - len(self.t_done)

    def submit(self, row: int, due: float) -> None:
        self.reqs.append(self.eng.submit(self.queries[row]))
        self.qrow.append(row)
        self.due.append(due)
        self.t_sub.append(self.clock())

    def mutate(self, kind: str, payload: np.ndarray) -> None:
        t = (self.eng.submit_upsert(payload) if kind == "insert"
             else self.eng.submit_delete(payload))
        self.tickets.append(t)

    def observe(self) -> None:
        t = self.clock()
        st = self.eng.stats
        nb = st["batches"]
        for i in range(self.n_disp, nb):
            self.t_disp[i] = t
        self.n_disp = nb
        recs = st["batch_records"]
        for i in range(self.n_rec, len(recs)):
            lo = len(self.t_done)
            hi = lo + recs[i]["n_real"]
            if hi > len(self.reqs) or not all(r.done for r in
                                              self.reqs[lo:hi]):
                raise RuntimeError("batch records do not map onto the "
                                   "requests in submission order")
            self.batch_of.extend([i] * (hi - lo))
            self.t_done.extend([t] * (hi - lo))
        if self.mem_probe is not None and len(recs) > self.n_rec:
            self.mem_peak = max(self.mem_peak, self.mem_probe())
        self.n_rec = len(recs)
        while self.tickets and self.tickets[0].done:
            tk = self.tickets.popleft()
            if tk.failed:
                self.failed_tickets += 1
            else:
                self.acked.append((tk, nb))

    def pump(self) -> bool:
        did = self.eng.pump()
        self.observe()
        return did

    def closed_loop(self, outstanding: int, end_row: int, seconds: float,
                    next_row: int) -> int:
        """Keep ``outstanding`` requests in flight for ``seconds``, each
        new one the next query row, never one twice; returns the next row.
        Needing a row at or past ``end_row`` is an error."""
        import jax
        ann = jax.profiler.TraceAnnotation
        t_close = self.clock() + seconds
        while self.clock() < t_close:
            with ann("bench.submit"):
                while self.outstanding < outstanding:
                    if next_row >= end_row:
                        raise RuntimeError(
                            f"the closed loop sent all {end_row} distinct "
                            "queries its mix draws; raise the mix's max_qps")
                    self.submit(next_row, self.clock())
                    next_row += 1
            with ann("bench.pump"):
                self.pump()
        return next_row

    def open_loop(self, arrivals: np.ndarray, rows: np.ndarray,
                  seconds: float, mutations=(), extra=None) -> None:
        """Send query ``rows[i]`` at ``arrivals[i]`` seconds from now and
        each mutation at its due time, whether or not earlier work has
        finished; return once every arrival is sent and ``seconds`` have
        passed."""
        import jax
        ann = jax.profiler.TraceAnnotation
        t0 = self.clock()
        i = mi = 0
        while True:
            now = self.clock() - t0
            with ann("bench.submit"):
                while i < len(arrivals) and arrivals[i] <= now:
                    self.submit(int(rows[i]), t0 + arrivals[i])
                    i += 1
                while mi < len(mutations) and mutations[mi].due_s <= now:
                    m = mutations[mi]
                    self.mutate(m.kind, extra[m.rows] if m.kind == "insert"
                                else m.rows)
                    mi += 1
            if i == len(arrivals) and now >= seconds:
                return
            with ann("bench.pump"):
                did = self.pump()
            if not did:
                wait = arrivals[i] - now if i < len(arrivals) else 5e-4
                with ann("bench.sleep"):
                    time.sleep(min(max(wait, 0.0), 5e-4))

    def finish(self, limit_s: float) -> None:
        """Pump until every request is answered or ``limit_s`` passed."""
        t_end = self.clock() + limit_s
        while len(self.t_done) < len(self.reqs) and self.clock() < t_end:
            if not self.pump():
                time.sleep(5e-4)

    def flush(self) -> None:
        self.eng.flush()
        self.observe()

    def flush_mutations(self) -> None:
        self.eng.flush_mutations()
        self.observe()

    def backlog_rows(self) -> int:
        return int(sum(len(t.payload) for t in self.tickets))


def _annotate(obj, attr: str, span: str) -> None:
    """Wrap one method of one object in a profiler span (traced runs)."""
    import jax
    fn = getattr(obj, attr, None)
    if fn is None:
        return

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(span):
            return fn(*a, **kw)
    setattr(obj, attr, wrapped)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def build_system(cfg: dict, base: np.ndarray, seed: int, buckets: tuple,
                 note: Callable) -> tuple:
    """The index and the engine the configuration describes."""
    from repro.core import IndexConfig, PilotANNIndex, SearchParams
    from repro.core.segments import SegmentedIndex, UpdateParams
    from repro.serving import ServeParams, ThroughputEngine
    icfg = IndexConfig(**cfg["index_config"], seed=int(seed))
    t0 = time.perf_counter()
    if cfg["index"] == "segmented":
        index = SegmentedIndex(icfg, base,
                               UpdateParams(**cfg.get("update_params", {})))
        built = index.base
    elif cfg["index"] == "static":
        index = built = PilotANNIndex(icfg, base)
    else:
        raise ValueError(f"unknown index kind {cfg['index']!r}")
    note("setup.build_s", time.perf_counter() - t0)
    for k, v in built.build_seconds.items():
        note(f"setup.build_seconds.{k}", v)
    note("setup.pilot_rows", built.n_pilot)
    t0 = time.perf_counter()
    params = SearchParams(**cfg["search_params"])
    eng = ThroughputEngine(index, params,
                           ServeParams(**cfg.get("serve_params", {}),
                                       buckets=tuple(buckets)))
    note("setup.engine_s", time.perf_counter() - t0)
    return index, built, eng, params


def shapes_of(built, index_cfg: dict) -> Dict[str, int]:
    a = built.arrays
    return {"d": int(built.d), "dp": int(built.reducer.d_primary),
            "R": int(index_cfg["R"]),
            "pilot_itemsize": int(a["primary"].dtype.itemsize),
            "pilot_id_itemsize": int(a["sub_neighbors"].dtype.itemsize),
            "full_id_itemsize": int(a["full_neighbors"].dtype.itemsize),
            "vec_itemsize": int(a["rot_vecs"].dtype.itemsize)}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def configure_compile_cache(root: Path) -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def warm_mutations(client: Client, corpus, mix: dict, serve_params: dict,
                    rng: np.random.Generator, live: np.ndarray,
                    next_gid: int) -> tuple:
    """Insert batches of every size the window's drains can coalesce and
    delete one ticket, so that the window compiles nothing.  Returns the
    live gids, the next gid and the first unused extra row."""
    from repro.core import device_build
    t_rows = int(mix.get("mutation_ticket_rows", 16))
    per_pump = int(serve_params.get("mutations_per_pump", 64))
    # the insert repair re-prunes the delta rows its new edges overflow in
    # one call padded to (power-of-two rows, multiple-of-8 candidates):
    # up to 512 rows, and each row's R edges plus up to two tickets' worth
    # of incoming ones
    R, d = client.eng.index.base.cfg.R, client.eng.index.d
    device_build.warm_prune_batch(
        [(1 << i, c, d) for i in range(10)
         for c in range(-(-(R + 1) // 8) * 8, R + 2 * t_rows + 1, 8)], R)
    row = 0
    for m in range(t_rows, per_pump + 1, t_rows):
        client.mutate("insert", corpus.extra[row:row + m])
        client.flush_mutations()
        live = np.concatenate([live, np.arange(next_gid, next_gid + m)])
        row += m
        next_gid += m
    pick = rng.choice(len(live), size=t_rows, replace=False)
    client.mutate("delete", live[pick])
    client.flush_mutations()
    live = np.delete(live, pick)
    return live, next_gid, row


def warm_rows(mix: dict, serve_params: dict) -> int:
    if not (mix.get("insert_rows_per_min") or mix.get("delete_rows_per_min")):
        return 0
    t_rows = int(mix.get("mutation_ticket_rows", 16))
    per_pump = int(serve_params.get("mutations_per_pump", 64))
    return sum(range(t_rows, per_pump + 1, t_rows))


def scheduled_inserts(mix: dict, seconds: float) -> int:
    t_rows = int(mix.get("mutation_ticket_rows", 16))
    return t_rows * len(btraffic.ticket_times(
        float(mix.get("insert_rows_per_min", 0)), t_rows, seconds, 0.25))


def make_corpus(cfg: dict, mix: dict, seed: int, seconds: float):
    """The run's corpus and the number of distinct queries its traffic
    sends (at most, in a closed loop); the queries after those warm the
    batch shapes."""
    geo = cfg["geometry"]
    n_open = btraffic.n_queries(mix, seconds)
    buckets = tuple(mix.get("buckets", btraffic.DEFAULT_BUCKETS))
    n_extra = (warm_rows(mix, cfg.get("serve_params", {}))
               + scheduled_inserts(mix, seconds))
    corpus = bdata.deep_like(seed, int(geo["rows"]), int(geo["dim"]),
                             n_extra=n_extra,
                             n_queries=n_open + max(buckets),
                             decay=float(geo["spectral_decay"]))
    return corpus, n_open


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_accelerator: bool = True,
             t_start: Optional[float] = None,
             note: Callable = lambda k, v: print(f"{k} {v}", flush=True),
             fault: Optional[Callable] = None) -> dict:
    """One run; returns the result object of the last line.  ``fault``,
    for tests only, is called with the engine before the window."""
    import jax
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_spec(root)
    cell, cfg_entry = find_cell(spec, workload)
    cfg = load_config(root, cfg_entry)
    mix = load_traffic(root, cell["traffic"])
    devs = jax.devices()
    kind = devs[0].device_kind
    if require_accelerator and devs[0].platform not in ACCELERATORS:
        raise NoAccelerator(f"JAX found no accelerator (backend "
                            f"{devs[0].platform!r})")
    if len(devs) < int(cell["chips"]):
        raise NoAccelerator(f"the cell needs {cell['chips']} chips, JAX "
                            f"found {len(devs)}")
    peaks = load_peaks(root, kind) if require_accelerator else None
    note("device.platform", devs[0].platform)
    note("device.kind", repr(kind))
    note("device.count", len(devs))
    note("compile_cache", configure_compile_cache(root))
    words = bdata.seed_words(seed, 4)
    rng = np.random.default_rng(words[2:])
    serve_cfg = cfg.get("serve_params", {})
    buckets = tuple(mix.get("buckets", btraffic.DEFAULT_BUCKETS))

    # -- data --------------------------------------------------------------
    t0 = time.perf_counter()
    corpus, n_open = make_corpus(cfg, mix, seed, seconds)
    n_warm_q = max(buckets)
    note("setup.data_s", time.perf_counter() - t0)

    # -- system ------------------------------------------------------------
    index, built, eng, params = build_system(cfg, corpus.base, words[0],
                                             buckets, note)
    k = params.k
    client = Client(eng, corpus.queries)
    t0 = time.perf_counter()
    for b in buckets:                   # every batch shape, once
        for j in range(b):
            client.submit(n_open + j % n_warm_q, client.clock())
        client.flush()
    n = len(corpus.base)
    live, next_gid, extra_row = np.arange(n, dtype=np.int64), n, 0
    mutating = cfg["index"] == "segmented" and warm_rows(mix, serve_cfg)
    if mutating:
        live, next_gid, extra_row = warm_mutations(
            client, corpus, mix, serve_cfg, rng, live, next_gid)
        for b in buckets:               # again, with a delta to merge
            for j in range(b):
                client.submit(n_open + j % n_warm_q, client.clock())
            client.flush()
    sched = btraffic.build_schedule(mix, rng, seconds, live_gids=live,
                                    next_gid=next_gid,
                                    first_extra_row=extra_row)
    next_row = 0
    if sched.loop == "closed" and sched.warmup_s > 0:
        next_row = client.closed_loop(sched.outstanding, n_open,
                                       sched.warmup_s, next_row)
    note("setup.warmup_s", time.perf_counter() - t0)
    dev0 = devs[0]
    mem = dev0.memory_stats() or {}
    note("setup.bytes_in_use", mem.get("bytes_in_use", "not reported"))
    # the build's scratch (NN-descent) sets the process's peak; the result
    # reports the bytes the served state holds through the window instead
    note("setup.peak_bytes_in_use", mem.get("peak_bytes_in_use",
                                            "not reported"))
    if "bytes_in_use" in mem:
        client.mem_peak = int(mem["bytes_in_use"])
        client.mem_probe = lambda: int(dev0.memory_stats()["bytes_in_use"])
    if fault is not None:
        fault(eng)

    # -- the window --------------------------------------------------------
    compiles = []
    listener = lambda name, *a, **kw: (compiles.append(name)
                                       if name == COMPILE_EVENT else None)
    trace_dir = root / ".bench_out" / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        for obj, attr, span in ((eng, "_dispatch", "engine.dispatch"),
                                (eng, "_drain_oldest", "engine.drain"),
                                (eng, "_apply_mutations", "engine.mutations"),
                                (index, "merge_with_deltas",
                                 "segments.merge_with_deltas"),
                                (index, "insert", "segments.insert"),
                                (index, "delete", "segments.delete"),
                                (index, "rotate_queries",
                                 "index.rotate_queries")):
            _annotate(obj, attr, span)
        jax.profiler.start_trace(str(trace_dir))
    first_req = len(client.reqs)
    first_batch = client.n_disp
    setup_s = time.perf_counter() - t_start
    jax.monitoring.register_event_duration_secs_listener(listener)
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = client.clock()
        if sched.loop == "closed":
            next_row = client.closed_loop(sched.outstanding, n_open,
                                           seconds, next_row)
        else:
            client.open_loop(sched.arrivals_s, np.arange(n_open), seconds,
                             sched.mutations, corpus.extra)
    t_window_end = client.clock()
    client.mem_probe = None
    peak = client.mem_peak
    last_batch = client.n_disp
    backlog = client.backlog_rows()
    # answers still due are served before anything else: stopping the
    # profiler takes tens of seconds, which they would otherwise wait
    n_window = len(client.reqs) - first_req
    client.finish(MAX_DRAIN_S)
    if trace:
        jax.profiler.stop_trace()
    jax.monitoring.unregister_event_duration_listener(listener)
    note("window.compiles", len(compiles))
    note("window.batches", last_batch - first_batch)
    note("window.mutation_backlog_rows", backlog)
    note("window.bytes_in_use_peak", peak)

    # -- mutations still queued --------------------------------------------
    if mutating:
        client.flush_mutations()
    lateness = np.array(client.t_sub[first_req:]) - np.array(
        client.due[first_req:])
    if len(lateness):
        note("window.generator_late_p99_ms",
             float(np.percentile(lateness, 99)) * 1e3)
    note("window.mutation_failures", client.failed_tickets)

    # -- read back every acknowledged insert -------------------------------
    inserts_missing = 0
    if mutating:
        born_ins = {}
        rows_seen = 0
        for tk, ep in client.acked:
            if tk.kind != "insert":
                continue
            exp = np.arange(n + rows_seen, n + rows_seen + len(tk.payload))
            if not np.array_equal(np.asarray(tk.gids), exp):
                raise RuntimeError("inserted rows did not get consecutive "
                                   "global ids in submission order")
            born_ins.update(dict.fromkeys(exp.tolist(), ep))
            rows_seen += len(tk.payload)
        died = {}
        for tk, ep in client.acked:
            if tk.kind == "delete":
                died.update(dict.fromkeys(np.asarray(tk.payload).tolist(), ep))
        n_all = n + rows_seen
        corpus_all = np.concatenate([corpus.base, corpus.extra[:rows_seen]])
        born = np.zeros(n_all, np.int32)
        for g, ep in born_ins.items():
            born[g] = ep
        dead = np.full(n_all, ref.NEVER, np.int32)
        for g, ep in died.items():
            dead[g] = ep
        alive = np.flatnonzero(dead[n:] == ref.NEVER) + n
        probe = alive[rng.choice(len(alive), size=min(512, len(alive)),
                                 replace=False)] if len(alive) else alive
        pclient = Client(eng, corpus_all)
        for g in probe:
            pclient.submit(int(g), pclient.clock())
        pclient.flush()
        inserts_missing = sum(int(g not in np.asarray(r.result[0]))
                              for g, r in zip(probe, pclient.reqs))
        del pclient
    else:
        corpus_all = corpus.base
        born = dead = None

    # -- per-query search counts (traced runs of a static index) -----------
    counters = None
    if trace and cfg["index"] == "static":
        rows = np.array(client.qrow[first_req:first_req + 256])
        _, _, st = built.search(corpus.queries[rows], params)
        counters = {key: float(np.mean(v)) for key, v in st.items()}
    shapes = shapes_of(built, cfg["index_config"])

    # -- the answers of the window -----------------------------------------
    win = slice(first_req, len(client.reqs))
    t_done = np.full(n_window, np.nan)
    t_done[:len(client.t_done) - first_req] = client.t_done[first_req:]
    due = np.array(client.due[win]) - t0
    t_done = t_done - t0
    t_disp = np.array([client.t_disp.get(b, np.nan) for b in
                       client.batch_of[first_req:]] +
                      [np.nan] * (n_window - len(client.batch_of)
                                  + first_req)) - t0
    done = np.isfinite(t_done)
    in_window = done & (t_done <= seconds)
    compare = in_window if sched.loop == "closed" else done
    reqs = client.reqs[first_req:]
    idx = np.flatnonzero(compare)
    got_ids = np.stack([np.asarray(reqs[j].result[0]) for j in idx]) \
        if len(idx) else np.zeros((0, k), np.int64)
    got_d = np.stack([np.asarray(reqs[j].result[1]) for j in idx]) \
        if len(idx) else np.zeros((0, k), np.float32)
    qrows = np.array(client.qrow[win])[idx]
    epochs = np.array(client.batch_of[first_req:], np.int64)[idx] \
        if len(idx) else np.zeros(0, np.int64)
    batches = list(eng.stats["batch_records"][first_batch:last_batch])
    failed_tickets = client.failed_tickets
    del client, eng, index, built
    gc.collect()

    # -- the reference, and the check --------------------------------------
    t0r = time.perf_counter()
    checks = compare_answers(corpus_all, corpus.queries, qrows, got_ids,
                             got_d, k, cfg["limits"], born=born, died=dead,
                             epoch=epochs)
    if mutating:
        checks["inserts_missing"] = {"value": int(inserts_missing),
                                     "max": cfg["limits"]["inserts_missing"]}
        checks["mutations_failed"] = {"value": int(failed_tickets),
                                      "max": cfg["limits"]["mutations_failed"]}
    note("check.reference_s", time.perf_counter() - t0r)
    correct = all(_within(c) for c in checks.values())

    # -- the trace ---------------------------------------------------------
    summary = None
    if trace:
        t0r = time.perf_counter()
        xp = btrace.find_xplane(str(trace_dir))
        if xp is None:
            raise RuntimeError("the profiler wrote no trace")
        summary = btrace.summarize(btrace.load_xplane(xp))
        shutil.rmtree(trace_dir, ignore_errors=True)
        note("trace.read_s", time.perf_counter() - t0r)

    rec = RunRecord(loop=sched.loop, window_s=seconds,
                    setup_s=setup_s, due=due, t_dispatch=t_disp,
                    t_done=t_done, completed_in_window=int(in_window.sum()),
                    drain_limit_s=t_window_end - t0 + MAX_DRAIN_S,
                    batches=batches, checks=checks, trace=summary,
                    counters=counters, shapes=shapes, peaks=peaks)
    metrics = {}
    for m in cell_metrics(spec, workload, trace):
        v = load_reader(root, m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    attempted = n_window
    failed = int(n_window - done.sum())
    device = {"platform": dev0.platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops,
                            "idle_gaps": summary.idle_by_host}
    out["checks"] = checks
    return out


def _within(c: dict) -> bool:
    v = c["value"]
    if "min" in c:
        return v >= c["min"]
    return v <= c["max"]


def compare_answers(corpus: np.ndarray, queries: np.ndarray,
                    qrows: np.ndarray, ids: np.ndarray, dists: np.ndarray,
                    k: int, limits: dict, *, born=None, died=None,
                    epoch=None) -> Dict[str, dict]:
    """The numbers that decide ``correct``, each with its limit:

    - ``recall_at_10``: the mean share of each answer's ids among the
      exact top-k of the rows live for it;
    - ``dist_gap``: the largest gap between a returned distance and the
      float64 distance of the returned row, over ``|q|^2 + |x|^2``;
    - ``stale_returned`` (given liveness): returned ids that were not live
      for the batch that returned them, or that name no row at all."""
    checks: Dict[str, dict] = {}
    if len(ids) == 0:
        checks["recall_at_10"] = {"value": 0.0,
                                  "min": limits["recall_at_10"]}
        return checks
    # one reference answer per distinct (query, liveness epoch)
    ep = np.zeros(len(qrows), np.int64) if epoch is None else np.asarray(
        epoch, np.int64)
    pairs, inv = np.unique(np.stack([qrows, ep], 1), axis=0,
                           return_inverse=True)
    live = {} if born is None else {"born": born, "died": died,
                                    "epoch": pairs[:, 1]}
    gt, _ = ref.exact_topk(corpus, queries[pairs[:, 0]], k, **live)
    gt = gt[inv.reshape(-1)]
    queries = queries[qrows]
    hits = [len(np.intersect1d(a[a >= 0], b)) for a, b in zip(ids, gt)]
    checks["recall_at_10"] = {"value": float(np.mean(hits)) / k,
                              "min": limits["recall_at_10"]}
    n = len(corpus)
    known = (ids >= 0) & (ids < n)
    rows = np.where(known, ids, -1)
    d64 = ref.sq_dists64(corpus, queries, rows)
    norms = ((queries.astype(np.float64) ** 2).sum(1)[:, None]
             + (corpus[np.where(known, ids, 0)].astype(np.float64) ** 2
                ).sum(-1))
    gap = np.where(known, np.abs(dists.astype(np.float64) - d64) / norms, 0)
    checks["dist_gap"] = {"value": float(gap.max()),
                          "max": limits["dist_gap"]}
    if born is not None:
        e = np.asarray(epoch)[:, None]
        ok = known & (born[np.where(known, ids, 0)] <= e) & (
            e < died[np.where(known, ids, 0)])
        stale = ((ids >= 0) & ~ok).sum()
        checks["stale_returned"] = {"value": int(stale),
                                    "max": limits["stale_returned"]}
    return checks
