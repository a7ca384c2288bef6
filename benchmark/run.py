#!/usr/bin/env python3
"""run.py — one cell of the benchmark, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearsal]

Finds the cell in BENCHMARK.json, its configuration under
`benchmark/configs/` with the plain reference its `codec` block names, its
traffic mix under `benchmark/traffic/`, the driver that plays the mix
under `benchmark/drivers/` and, for `--trace 1`,
each of the cell's per-layer metrics under `benchmark/layer_metrics/` with
its reader under `benchmark/readers/`.  Starts the configuration's server
as a child through `serve.py` (the only process that touches JAX), on a
copy of the seed's sealed volumes from `benchmark/.cache/` or, on a seed's
first run in a checkout, on volumes loaded through the served write path.

Standard output carries one line, the last thing written, and only when
the run reached its end: the contract's JSON object, with `compared` as
its last key (each count held against the reference, beside its limit:
the same lines end standard error).  `--trace 0` gives the cell's
end-to-end metrics, `--trace 1` its per-layer metrics.  A run that
finds no accelerator, another codec than the configuration expects, or a
host codec at work prints no line and exits nonzero.  Progress, sample
counts and the server's log tail go to standard error; per-operation walls
and the evidence the readers saw go to `benchmark/out/<workload>/`.

`--rehearsal` is the same walk at a tiny size on the CPU (XLA codec, no
Pallas) for a sandbox with no chip: its line says `"platform": "cpu"`, it
computes no device metric, and nothing from it belongs in PERF.md.
"""

from __future__ import annotations

import argparse
import functools
import http.client
import importlib.util
import json
import os
import shutil
import signal
import sys
import time
import traceback

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
from harness import BenchFailure, ROOT, check, load_json, say  # noqa: E402

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
DEADLINE_S = 340  # the driver allows 360, and 1200 to a checkout's first run
FIRST_RUN_DEADLINE_S = 1100


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, found by the name a data file gives."""
    path = os.path.join(BENCH, kind, name + ".py")
    check(os.path.exists(path), f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown: dict | None = None,
                compared: dict | None = None) -> str:
    """The contract's line; `compared`, each number held against the
    reference beside its limit, comes last."""
    line = dict(zip(RESULT_KEYS, (bool(correct), int(attempted), int(failed),
                                  metrics, device)))
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared or {}
    return json.dumps(line)


def judge(result: dict) -> tuple[bool, dict]:
    """-> (`correct`, `compared`) of what a driver returned: its
    `compared` ([wrong, of] by name) as the line carries it, each count
    beside its limit.  Every comparison here is exact (sha256, sizes, an
    answer's fields), so every limit is 0."""
    compared = {name: {"value": wrong, "limit": 0, "of": of}
                for name, (wrong, of) in result["compared"].items()}
    return not result["failures"] and all(
        c["value"] <= c["limit"] for c in compared.values()), compared


class Tracer:
    """Opens and closes jax.profiler in the server child round a slice of
    the window, and keeps the /perf snapshots of the slice's two edges."""

    def __init__(self, srv, trace_dir: str, enabled: bool):
        self.srv, self.dir, self.enabled = srv, trace_dir, enabled
        self.active = False
        self.slice: dict | None = None

    def start(self) -> None:
        if not self.enabled or self.slice is not None:
            return
        self.srv.control(f"trace_start {self.dir}")
        self.active = True
        self.slice = {"perf0": self.srv.perf(), "t0": time.time()}

    def stop(self) -> None:
        if not self.active:
            return
        self.slice.update(t1=time.time(), perf1=self.srv.perf())
        self.srv.control("trace_stop")
        self.active = False


class Cell:
    """What a driver is handed."""

    def __init__(self, args, bench: dict):
        self.seed, self.seconds = args.seed, args.seconds
        self.rehearsal, self.traced = args.rehearsal, bool(args.trace)
        self.workload = next((w for w in bench["workloads"]
                              if w["name"] == args.workload), None)
        check(self.workload, f"BENCHMARK.json has no workload "
              f"{args.workload!r}")
        entry = next(c for c in bench["configs"]
                     if c["name"] == self.workload["config"])
        self.config = load_json(os.path.join(ROOT, entry["file"]))
        self.codec = self.config["codec"]
        self.ref = harness.reference_of(self.codec)
        self.traffic = load_json(os.path.join(
            BENCH, "traffic", self.workload["traffic"] + ".json"))
        self.sizes = dict(self.config, **(self.config["rehearsal"]
                                          if self.rehearsal else {}))
        self.work = os.path.join(harness.WORK_DIR, self.workload["name"])
        self.out = os.path.join(harness.OUT_DIR, self.workload["name"])
        for d in (self.work, self.out):
            shutil.rmtree(d, ignore_errors=True)
            os.makedirs(d)
        self.srv = harness.Server(self.config, self.work, self.rehearsal,
                                  self.traced)
        self.tracer = Tracer(self.srv, os.path.join(self.work, "trace"),
                             self.traced)
        self.volumes: list[dict] = []
        self.device: dict | None = None
        self.window: dict = {}
        self.setup_s: float | None = None
        self.first_run = False
        self.phases: dict[str, float] = {}

    # -- set-up ---------------------------------------------------------------

    def phase(self, name: str, t0: float) -> None:
        self.phases[name] = round(time.time() - t0, 3)
        harness.check_file_sizes(harness.WORK_DIR, harness.CACHE_DIR,
                                 harness.OUT_DIR, harness.compile_cache())
        say(f"{name}: {self.phases[name]} s")

    def volume_cache(self):
        return harness.VolumeCache(self.config["name"], self.codec,
                                   self.seed, self.rehearsal)

    def post_steps(self, steps: list[dict], vids: list[int]) -> None:
        """The untimed POSTs a data file lists, the configuration's codec
        tag filled in for `{codec}`."""
        harness.post_steps(self.srv, steps, vids, codec=self.codec["tag"])

    def volumes_up(self) -> None:
        """The server running on a copy of the seed's sealed volumes, which
        is what a restarted volume server does.  A seed's first run in a
        checkout builds them first, with a server of its own, so that the
        measured server starts from the same state in every run."""
        cache = self.volume_cache()
        t0 = time.time()
        self.volumes = cache.lookup()
        if self.volumes is None:
            self.first_run = True
            self.volumes = self.build_volumes(cache)
            t0 = time.time()
        cache.restore(self.volumes, self.srv.data_dir)
        self.phase("restore_volumes", t0)
        t0 = time.time()
        self.srv.start()
        self.phase("server_start", t0)
        self._check_sample()

    def build_volumes(self, cache) -> list[dict]:
        """Load the configuration's volumes through the served write path
        (one writer, so the seed fixes every offset), seal them, compute
        the reference and leave all of it in the volume cache."""
        t0 = time.time()
        self.srv.start()
        self.phase("build.server_start", t0)
        t0 = time.time()
        nb = self.sizes["needle_bytes"]
        loaded = []
        for i in range(self.sizes["volumes"]):
            loaded.append(harness.load_volume(
                self.srv, f"{self.config['collection']}{i}",
                self.sizes["volume_bytes"], nb["min"], nb["max"],
                harness.make_rng(self.seed, 1, i)))
        self.phase("build.load_volumes", t0)
        t0 = time.time()
        self.post_steps(self.config["seal_call"]["steps"],
                        [v["vid"] for v in loaded])
        self.phase("build.seal_volumes", t0)
        t0 = time.time()
        volumes = [harness.describe_volume(self.srv, v, self.ref, self.codec)
                   for v in loaded]
        self.phase("build.reference", t0)
        t0 = time.time()
        cache.store(volumes, self.srv.data_dir)
        self.srv.stop()
        shutil.rmtree(self.srv.data_dir)
        self.phase("build.store_volumes", t0)
        return volumes

    def _check_sample(self) -> None:
        """A restored volume answers for a sample of its needles."""
        t0 = time.time()
        rng = harness.make_rng(self.seed, 3)
        conn = http.client.HTTPConnection(self.srv.volume, timeout=120)
        try:
            for v in self.volumes:
                for i in rng.choice(len(v["needles"]), 4, replace=False):
                    n = v["needles"][int(i)]
                    status, body = harness.read_needle(conn, n[0])
                    check(harness.needle_ok(status, body, n),
                          f"restored volume {v['vid']}: GET {n[0]} -> "
                          f"{status}, {len(body)} bytes")
        finally:
            conn.close()
        self.phase("check_sample", t0)

    def traffic_setup(self) -> None:
        t0 = time.time()
        self.post_steps(self.traffic["setup"],
                        [v["vid"] for v in self.volumes])
        self.phase("traffic_setup", t0)

    # -- the window's edges, called by the driver -----------------------------------

    def check_device(self, perf: dict) -> None:
        """What the server says it ran on; a run on anything else than the
        configuration expects is no run."""
        blocks = perf["codecs"]
        check(blocks, "/perf carries no codec block after the warm-up")
        b = blocks[0]
        self.device = {"platform": b.get("platform"),
                       "kind": b.get("device_kind"),
                       "count": b.get("device_count")}
        if self.rehearsal:
            return
        want = self.config["expect"]
        for b in blocks:
            check(b.get("platform") == want["platform"] and
                  b.get("interpret") is want["interpret"],
                  f"the server resolved a codec this configuration does "
                  f"not expect: {b}")
        have = {b.get("codec") for b in blocks}
        check(set(want["codecs"]) <= have,
              f"expected {want['codecs']} among the codecs, found {have}")
        check(self.device["count"] == self.workload["chips"],
              f"the cell asks for {self.workload['chips']} chip(s), the "
              f"server sees {self.device['count']}")
        for row in perf["roofline"]["rows"]:
            check(not (row["kernel"] in want["device_kernels"] and
                       row["backend"] == "host"),
                  f"/perf shows a host row for {row['kernel']}: a host "
                  f"codec ran: {row}")

    def window_begins(self) -> None:
        harness.check_file_sizes(harness.WORK_DIR, harness.CACHE_DIR)
        perf = self.srv.perf()
        self.check_device(perf)
        self.window = {"perf0": perf, "log0": self.srv.log_mark(),
                       "t0": time.time()}
        self.setup_s = self.window["t0"] - harness.T_START
        say(f"window begins after {self.setup_s:.2f} s of set-up on "
            f"{self.device}")

    def window_ended(self) -> None:
        self.window.update(t1=time.time(), log1=self.srv.log_mark(),
                           perf1=self.srv.perf())
        self.check_device(self.window["perf1"])
        say(f"window ended after {self.window['t1'] - self.window['t0']:.2f} s")


def per_layer_metrics(cell: Cell, bench: dict, result: dict) -> tuple:
    """-> (metrics, evidence) of the traced run."""
    import trace_reduce
    table = harness.kernel_table()
    peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    sl = cell.tracer.slice
    if sl is not None and "t1" in sl:
        sl["bytes"] = sum(o["bytes"] for o in result["ops"]
                          if o["t0"] >= sl["t0"] and
                          o.get("t1", o["t0"]) <= sl["t1"])
        path = trace_reduce.find_xplane(cell.tracer.dir)
        check(path, f"the profiler left no .xplane.pb under "
              f"{cell.tracer.dir}")
        planes = trace_reduce.load_planes(path)
        with open(os.path.join(cell.out, "trace_summary.json"), "w") as f:
            json.dump(trace_reduce.summarize(planes), f, indent=1)
        sl["trace"] = trace_reduce.reduce(planes, table)
        sl["xplane_bytes"] = os.path.getsize(path)
    else:
        sl = None
    if not cell.rehearsal:
        check(cell.device["kind"] in peaks,
              f"peaks.json knows no device {cell.device['kind']!r}")
    cell.window["log"] = cell.srv.log_between(cell.window["log0"],
                                              cell.window["log1"])
    ev = {"ops": result["ops"], "window": cell.window, "slice": sl,
          "kernels": table["kernels"],
          "peak": peaks.get(cell.device["kind"])}
    metrics = {}
    for m in bench["per_layer"]:
        if cell.workload["name"] not in m.get("workloads",
                                              [cell.workload["name"]]):
            continue
        spec = load_json(os.path.join(BENCH, "layer_metrics",
                                      m["name"] + ".json"))
        value = load_module("readers", spec["reader"]).read(
            ev, spec["params"])
        if value is not None:  # a reader that finds nothing returns nothing
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, ev


def run(args, cell: Cell, bench: dict) -> str:
    driver = load_module("drivers", cell.traffic["driver"])
    cell.volumes_up()
    lock = harness.AdminLock(cell.srv) if cell.config["admin_lock"] else None
    cell.traffic_setup()
    t0 = time.time()
    result = driver.run(cell)  # warms up, then calls window_begins()
    cell.phase("warmup_and_window", t0)
    if lock is not None:
        lock.release()
    device = cell.srv.control("device")["device"]
    check((device["platform"], device["kind"], device["count"]) ==
          tuple(cell.device.values()),
          f"JAX reports {device}, /perf said {cell.device}")
    server_exit = cell.srv.stop()
    for why in result["failures"][:10]:
        say(f"FAILED OPERATION: {why}")
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    result["metrics"]["setup_s"] = cell.setup_s
    say(f"end to end: {result['metrics']} over {result['attempted']} "
        f"operations, {result['failed']} failed")
    breakdown = None
    report = {"workload": cell.workload["name"], "seed": cell.seed,
              "seconds": cell.seconds, "traced": cell.traced,
              "rehearsal": cell.rehearsal, "first_run": cell.first_run,
              "device": device, "phases": cell.phases,
              "server_exit": server_exit, "end_to_end": result["metrics"],
              "attempted": result["attempted"], "failed": result["failed"],
              "failures": result["failures"][:50]}
    if cell.traced:
        metrics, ev = per_layer_metrics(cell, bench, result)
        sl = ev["slice"]
        tr = sl["trace"] if sl else None
        if tr and tr["devices"]:
            device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
            breakdown = {"device_ops": tr["device_ops"],
                         "idle_gaps": tr["idle_gaps"]}
        else:
            check(cell.rehearsal, "the trace shows no operation on any "
                  "device: the cell did not drive the device path")
        say(f"per layer: { {k: v['value'] for k, v in metrics.items()} }")
        report["per_layer"] = metrics
        report["slice"] = sl and {k: v for k, v in sl.items()
                                  if k not in ("perf0", "perf1")}
    else:
        metrics = {name: {"value": value, "unit": units[name]}
                   for name, value in result["metrics"].items()}
    with open(os.path.join(cell.out, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    with open(os.path.join(cell.out, "ops.json"), "w") as f:
        json.dump(result["ops"], f)
    shutil.rmtree(cell.srv.data_dir, ignore_errors=True)
    correct, compared = judge(result)
    for name, c in compared.items():  # the last lines on standard error
        say(f"compared {name}: {c['value']} of {c['of']}, limit {c['limit']}")
    say(f"correct: {correct}")
    return result_line(correct, result["attempted"], result["failed"],
                       metrics, device, breakdown, compared)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny CPU walk-through; proves nothing about a chip")
    args = ap.parse_args()

    # standard output belongs to the result line alone: whatever else this
    # process or a child might write there goes to standard error
    result_out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)

    if not os.path.exists(os.path.join(ROOT, "seaweedfs_tpu", "__main__.py")):
        say(f"{ROOT} holds no seaweedfs_tpu: the benchmark runs from the "
            f"root of a weedtpu checkout")
        return 1
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if args.seconds is None:
        args.seconds = bench["run_seconds"]

    def on_signal(signum, frame):
        raise BenchFailure(f"signal {signum} (the alarm ends a run that "
                           f"would outlast the driver's allowance)")
    signal.signal(signal.SIGALRM, on_signal)
    signal.signal(signal.SIGTERM, on_signal)

    cell = None
    try:
        cell = Cell(args, bench)
        cached = os.path.exists(cell.volume_cache().manifest_path)
        signal.alarm(DEADLINE_S if cached else FIRST_RUN_DEADLINE_S)
        line = run(args, cell, bench)
    except Exception as e:  # every failure, the alarm's included: exit 1
        traceback.print_exc()
        if cell is not None:
            say("---- end of the server's log ----\n" + cell.srv.log_tail())
            say(f"FAILED: {e}\n(work directory kept: {cell.work})")
        else:
            say(f"FAILED: {e}")
        return 1
    finally:
        signal.alarm(0)
        if cell is not None:
            cell.srv.stop()
    print(line, file=result_out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
