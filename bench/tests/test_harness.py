"""CPU tests of the benchmark harness.  No test loads a TPU library."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import harness, layers, reference, traffic
from bench import trace as btrace
from bench.tests.conftest import REPO, make_tiny_root, quiet

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())


def _line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_benchmark_json_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(SPEC)) <= 64 * 1024
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert ".." not in p and not p.startswith("/")
    assert len(SPEC["command"]) <= 32 and all(map(_line, SPEC["command"]))
    names = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"])
        assert _line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
        names.add(c["name"])
    cells = set()
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cells.add(w["name"])
    assert {c["config"] for c in SPEC["workloads"]} == names
    metric_names = set()
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in metric_names
        metric_names.add(m["name"])
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]]
        # every cell that reports a per-layer metric reports what it moves
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for cell in cells:
        got = {m["name"] for m in harness.cell_metrics(SPEC, cell, False)}
        assert "setup_s" in got and len(got) >= 2
        assert harness.cell_metrics(SPEC, cell, True)


def test_every_named_file_loads():
    for c in SPEC["configs"]:
        cfg = harness.load_config(REPO, c)
        assert cfg["name"] == c["name"]
        assert {"geometry", "index_config", "search_params",
                "limits"} <= set(cfg)
    for w in SPEC["workloads"]:
        harness.load_traffic(REPO, w["traffic"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(harness.load_reader(REPO, m["name"]))
    assert harness.load_peaks(REPO, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.load_peaks(REPO, "no such chip")


def test_traffic_is_fixed_by_the_seed():
    mix = {"loop": "open", "rate_qps": 50, "insert_rows_per_min": 600,
           "delete_rows_per_min": 600, "mutation_ticket_rows": 4}
    live = np.arange(1000)
    make = lambda seed: traffic.build_schedule(
        mix, np.random.default_rng(seed), 10.0, live_gids=live,
        next_gid=1000)
    a, b, c = make(7), make(7), make(8)
    assert np.array_equal(a.arrivals_s, b.arrivals_s)
    assert [m.rows.tolist() for m in a.mutations] == \
        [m.rows.tolist() for m in b.mutations]
    # another seed: the same number of queries and the same gaps, in
    # another order
    assert len(a.arrivals_s) == len(c.arrivals_s) == 500
    assert not np.array_equal(a.arrivals_s, c.arrivals_s)
    da, dc = (np.round(np.diff(x.arrivals_s), 9) for x in (a, c))
    assert np.isin(da, dc).sum() >= len(da) - 1
    assert a.arrivals_s.min() == 0 and a.arrivals_s.max() < 10.0
    # deletes pick rows live when they are sent, never one twice
    deleted = np.concatenate([m.rows for m in a.mutations
                              if m.kind == "delete"])
    assert len(np.unique(deleted)) == len(deleted) == 100
    inserted = np.concatenate([m.rows for m in a.mutations
                               if m.kind == "insert"])
    assert inserted.tolist() == list(range(100))


class _Req:
    done = False


class _Engine:
    """Answers every queued request at each pump, as one batch."""

    def __init__(self):
        self.queue = []
        self.stats = {"batches": 0, "batch_records": []}

    def submit(self, q):
        self.queue.append(_Req())
        return self.queue[-1]

    def pump(self):
        if not self.queue:
            return False
        for r in self.queue:
            r.done = True
        self.stats["batches"] += 1
        self.stats["batch_records"].append({"n_real": len(self.queue)})
        self.queue = []
        return True


def test_closed_loop_sends_each_query_once_and_reads_window_memory():
    mix = {"loop": "closed", "outstanding": 4, "max_qps": 50,
           "warmup_s": 1}
    assert traffic.n_queries(mix, 3.0) == 200
    ticks = iter(np.arange(0, 10 ** 4, 1e-3))    # a clock of 1 ms a read
    client = harness.Client(_Engine(), np.zeros((200, 2), np.float32),
                            clock=lambda: next(ticks))
    held = iter(range(1000, 10 ** 6, 1000))
    client.mem_probe = lambda: next(held)
    client.closed_loop(4, 200, 0.05, 0)
    assert client.qrow == list(range(len(client.qrow)))
    # read once per batch seen done
    assert client.mem_peak == 1000 * client.eng.stats["batches"] > 0
    with pytest.raises(RuntimeError, match="max_qps"):
        client.closed_loop(4, 200, 60.0, len(client.qrow))


def _record(**kw):
    base = dict(loop="open", window_s=10.0, setup_s=1.0,
                due=np.zeros(0), t_dispatch=np.zeros(0), t_done=np.zeros(0),
                completed_in_window=0, drain_limit_s=70.0, batches=[],
                checks={"recall_at_10": {"value": 0.5, "min": 0.1}})
    base.update(kw)
    return harness.RunRecord(**base)


def test_tail_is_over_all_requests_and_rate_over_the_window():
    rng = np.random.default_rng(0)
    due = np.sort(rng.uniform(0, 10, 1000))
    lat = rng.exponential(0.1, 1000)
    lat[:5] = 3.0                       # five slow requests at the start
    t_done = due + lat
    t_done[7] = np.nan                  # one never answered
    run = _record(due=due, t_done=t_done, t_dispatch=due + 0.01)
    read = lambda m: harness.load_reader(REPO, m)(run)
    all_lat = np.where(np.isfinite(t_done), t_done, 70.0) - due
    assert read("latency_p99_ms") == pytest.approx(
        np.percentile(all_lat, 99) * 1e3)
    assert read("latency_p50_ms") == pytest.approx(
        np.percentile(all_lat, 50) * 1e3)
    # the median of per-second chunk tails would hide the slow start
    chunks = [np.percentile(all_lat[(due >= s) & (due < s + 1)], 99)
              for s in range(10)]
    assert read("latency_p99_ms") > np.median(chunks) * 1e3
    assert read("queue_wait_p99_ms") == pytest.approx(10.0)
    closed = _record(loop="closed", completed_in_window=2345)
    assert harness.load_reader(REPO, "qps")(closed) == 234.5
    assert harness.load_reader(REPO, "latency_p99_ms")(closed) is None


def test_roofline_bytes_match_hand_arithmetic():
    c = {"fes_dist": 100.0, "pilot_dist": 900.0, "pilot_expanded": 50.0,
         "refine_dist": 200.0, "final_dist": 300.0, "final_expanded": 20.0}
    s = {"d": 96, "dp": 48, "R": 32, "pilot_itemsize": 4,
         "pilot_id_itemsize": 4, "full_id_itemsize": 4, "vec_itemsize": 4}
    assert layers.stage1_bytes(c, s) == 1000 * 48 * 4 + 50 * 32 * 4
    assert layers.stage23_bytes(c, s) == (200 * 48 * 4 + 300 * 96 * 4
                                          + 20 * 32 * 4)
    summ = btrace.TraceSummary(window_s=1.0, busy_s=0.5, n_devices=1,
                               module_s={"jit_pilot_fn": 0.2},
                               module_runs={"jit_pilot_fn": 10})
    run = _record(loop="closed", trace=summ, counters=c, shapes=s,
                  batches=[{"n_real": 128}] * 3,
                  peaks={"hbm_bytes_per_s": 819e9})
    us = 0.2 / (10 * 128) * 1e6
    assert layers.us_per_query(run, "jit_pilot_fn") == pytest.approx(us)
    pct = 100 * (1000 * 48 * 4 + 50 * 32 * 4) / 819e9 / (us * 1e-6)
    assert harness.load_reader(REPO, "stage1_roofline")(run) == \
        pytest.approx(pct)
    # no executable of that name in the trace: the reader stays silent
    assert harness.load_reader(REPO, "stage23_roofline")(run) is None
    assert harness.load_reader(REPO, "stage1_roofline")(
        _record(loop="closed")) is None


def _by_hand(events):
    """Busy seconds and module seconds by a 1 us timeline."""
    (w0, dur), = [(e[3], e[4]) for e in events if e[2] == "bench.window"]
    n = int(dur // 1000) + 1
    line = np.zeros(n, bool)
    mods = {}
    for p, ln, name, s, d in events:
        if not btrace.is_device_plane(p):
            continue
        a = int(max(0, (s - w0) // 1000))
        b = int(min(n, -(-(s + d - w0) // 1000)))
        if ln == btrace.OP_LINE and b > a:
            line[a:b] = True
        if ln == btrace.MODULE_LINE and s < w0 + dur and s + d > w0:
            k = btrace.module_name(name)
            mods[k] = mods.get(k, 0) + (min(s + d, w0 + dur) - max(s, w0))
    return line.sum() / 1e6, {k: v / 1e9 for k, v in mods.items()}, dur / 1e9


def test_trace_reduction_on_a_recorded_trace():
    events = btrace.read_events(Path(__file__).parent / "data"
                                / "trace_small.json.gz")
    s = btrace.summarize(events)
    busy, mods, window = _by_hand(events)
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(window)
    assert s.busy_s == pytest.approx(busy, abs=2e-6 * len(events))
    assert s.module_s.keys() == mods.keys()
    for k in mods:
        assert s.module_s[k] == pytest.approx(mods[k])
    assert {"jit_pilot_fn", "jit_cpu_fn"} <= set(s.module_s)
    assert 0 < s.idle_pct < 100
    idle = sum(v for _, v in s.idle_by_host)
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert len(s.top_ops) <= 10 and len(s.idle_by_host) <= 10


def test_reference_equals_float64_numpy():
    from bench import data
    c = data.deep_like(11, 2000, 96, n_extra=0, n_queries=50)
    x64, q64 = c.base.astype(np.float64), c.queries.astype(np.float64)
    full = ((q64[:, None, :] - x64[None]) ** 2).sum(-1)
    ids, d = reference.exact_topk(c.base, c.queries, 10)
    assert np.array_equal(ids, np.argsort(full, axis=1, kind="stable")[:, :10])
    assert np.allclose(d, np.sort(full, axis=1)[:, :10], rtol=0, atol=0)
    # liveness by epoch: rows born later or dead earlier are never answers
    born = np.zeros(2000, np.int32)
    born[:500] = 3
    died = np.full(2000, reference.NEVER, np.int32)
    died[500:1000] = 2
    epoch = np.arange(50) % 5
    ids, _ = reference.exact_topk(c.base, c.queries, 10, born=born,
                                  died=died, epoch=epoch)
    for e, row, q in zip(epoch, ids, full):
        live = (born <= e) & (e < died)
        want = np.flatnonzero(live)[np.argsort(q[live], kind="stable")[:10]]
        assert np.array_equal(row, want)


def test_generator_is_deterministic_and_deep_like():
    from bench import data
    a = data.deep_like(2 ** 31 + 5, 3000, 96, n_extra=10, n_queries=20)
    b = data.deep_like(2 ** 31 + 5, 3000, 96, n_extra=10, n_queries=20)
    assert np.array_equal(a.base, b.base) and np.array_equal(a.queries,
                                                             b.queries)
    assert a.base.shape == (3000, 96) and a.extra.shape == (10, 96)
    # the spectrum decays: the top 48 principal directions hold most of it
    s = np.linalg.svd(a.base - a.base.mean(0), compute_uv=False) ** 2
    assert s[:48].sum() / s.sum() > 0.75


def test_a_new_cell_is_only_new_files(tmp_path):
    root = make_tiny_root(tmp_path)
    (root / "bench" / "traffic" / "tiny-trickle.json").write_text(json.dumps(
        {"loop": "open", "rate_qps": 30, "buckets": [8, 16]}))
    (root / "bench" / "metrics" / "answers.py").write_text(
        '"""Requests answered."""\n\n\ndef read(run):\n'
        '    return float(len(run.due))\n')
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.trickle",
                              "config": "tiny-deep-500k",
                              "traffic": "tiny-trickle", "chips": 1,
                              "why": "a throwaway cell"})
    spec["end_to_end"].append({"name": "answers", "unit": "count",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["tiny.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = harness.run_cell("tiny.trickle", 5, 2.0, False, root=root,
                           require_accelerator=False, note=quiet)
    assert out["correct"], out["checks"]
    assert out["metrics"]["answers"]["value"] == 60
    assert {"recall_at_10", "setup_s", "answers"} <= set(out["metrics"])
    assert list(out)[-1] == "checks"


def _run_py(cwd: Path, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", "--workload",
                           SPEC["workloads"][0]["name"], "--seed",
                           "3000000000", "--seconds", "1", "--trace", "0",
                           *extra], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_run_without_an_accelerator_prints_no_result():
    r = _run_py(REPO)
    assert r.returncode != 0
    assert "metrics" not in r.stdout and "no accelerator" in r.stderr


def test_run_without_the_program_prints_no_result(tmp_path):
    import shutil
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    r = _run_py(tmp_path)
    assert r.returncode != 0 and "metrics" not in r.stdout
