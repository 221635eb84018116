"""Run one cell of the benchmark once, on the chip it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``, whose ``driver`` names the loop in
``bench/drivers``).  The run loads and warms up (``setup_s``), measures for
``--seconds`` with tracing off (``--trace 0``: the end-to-end metrics) or
on (``--trace 1``: the per-layer metrics, each read by
``bench/metrics/<name>.py``), then checks what the window produced against
the plain reference and the recorded checkpoints.  Readings and the checks
go to stderr, the checks last; the last line of stdout is the result.

It exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.common import (  # noqa: E402
    CompileCounter, Recorder, load_json, load_module, log, process_start_epoch,
)


class NoChip(RuntimeError):
    pass


def require_chip(n: int):
    """The devices of the cell, or ``NoChip``: a TPU only, never a fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devices)}")
    return devices[:n]


def cell_spec(root: str, name: str):
    """(manifest, cell, config, traffic) of a cell, found by name."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic", cell["traffic"] + ".json"))
    return manifest, cell, config, traffic


def metrics_of(manifest, cell_name: str, trace: bool):
    """The metric entries this cell reports in this kind of run."""
    entries = manifest["per_layer"] if trace else manifest["end_to_end"]
    return [m for m in entries if "workloads" not in m or cell_name in m["workloads"]]


class Run:
    """Everything one run knows; drivers fill it, metric readers read it."""

    def __init__(self, root, manifest, cell, config, traffic, seed, seconds, trace):
        self.root, self.manifest, self.cell = root, manifest, cell
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.rec = Recorder()
        self.values = {}
        self.kernel_calls = {}
        self.attempted = self.failed = 0
        self.served = []
        self.crs = []            # every DeltaCR of the cell; drivers fill it
        self.peaks = None
        self.red = None


def reference_check(run, *, control: bool = False):
    """Widest gap by which a served greedy token's logit lies below the
    float32 reference's best, over the sampled sequences (with ``control``:
    the gap of the token the float8 control puts first instead)."""
    import numpy as np

    from bench.model import family

    fam = family(run.config, run.root)
    ref = fam.Reference(fam.dims(run.config), control=control)
    cut = int(run.traffic["prompt_tokens"]) - 1     # the prompt pass stops before its last token
    width = max(len(t) for t, _ in run.served) - cut
    width = -(-width // 128) * 128           # one compiled length for every sequence
    worst, n = 0.0, 0
    for toks, mask in run.served:
        rest = np.zeros(width, np.int32)
        check = np.zeros(width, bool)
        rest[: len(toks) - cut], check[: len(toks) - cut] = toks[cut:], mask[cut:]
        gaps = ref.gaps(run.system.params, np.asarray(toks[:cut], np.int32), rest, check)
        worst = max(worst, float(gaps.max()))
        n += int(check[1:].sum())
    return worst, n


def main(argv=None) -> int:
    t_proc = process_start_epoch()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    use_checkout_dirs()
    manifest, cell, config, traffic = cell_spec(ROOT, args.workload)
    try:
        devices = require_chip(int(cell["chips"]))
    except NoChip as exc:
        log(f"refusing to run: {exc}")
        return 3
    return run_cell(ROOT, manifest, cell, config, traffic, args, devices, t_proc)


def use_checkout_dirs() -> None:
    """Point JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    and the TPU runtime's logs at ``<checkout>/.tpu_logs`` before JAX is
    imported, whatever the environment says: fixed paths inside the
    checkout, so that two checkouts never share programs or files."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["TPU_LOG_DIR"] = os.path.join(ROOT, ".tpu_logs")


def prepare(root):
    """Process-wide set-up: the program on the path, the compile cache on,
    and a counter of compiles."""
    import jax

    sys.path.insert(0, os.path.join(root, "src"))
    from repro.launch.cache import enable_compile_cache

    cache = enable_compile_cache()
    # every program goes to the persistent cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache, CompileCounter()


def measure(root, manifest, cell, config, traffic, seed, seconds, trace, devices, counter, t_proc):
    """Set up, measure the window, and check it: everything but the
    reference.  Returns the ``Run``; the program's state is freed, the
    benchmark's weights are kept in ``run.system.params``."""
    import importlib

    import jax

    from bench import state as bstate
    from bench import trace as btrace

    kind = devices[0].device_kind
    peaks = load_json(os.path.join(root, "bench", "peaks.json"))
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    run = Run(root, manifest, cell, config, traffic, seed, seconds, bool(trace))
    run.peaks = peaks[kind]
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    driver.setup(run)
    run.setup_s = time.time() - t_proc
    log(f"setup_s {run.setup_s:.3f} (process start to first timed op)")

    before = counter.snapshot()
    logdir = os.path.join(root, ".bench_trace", cell["name"])
    if run.trace:
        import shutil

        shutil.rmtree(logdir, ignore_errors=True)
        btrace.capture_start(logdir)
        run.rec.tracing = True
    run.rec.active = True
    t0 = time.perf_counter()
    with run.rec.span("window"):
        driver.window(run, seconds)
    run.window_s = time.perf_counter() - t0
    run.rec.active = False
    run.rec.tracing = False
    if run.trace:
        path = btrace.capture_stop(logdir)
        run.red = btrace.reduce(btrace.load(path))
    after = counter.snapshot()
    run.in_window = {k: after[k] - before[k] for k in after}
    run.in_window["programs"] = counter.names[before["programs"]:after["programs"]]
    run.peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)

    run.image_states = bstate.StateLog()
    run.image_states.at_checkpoint = run.states.at_checkpoint
    run.values["pool_pages"] = run.system.pool.num_pages
    run.values["pool_pages_in_use_peak"] = run.system.pages_peak
    driver.after_window(run)
    run.health = [cr.health() for cr in run.crs]
    run.rb = run.states.mismatches()
    run.im = run.image_states.mismatches()
    for c in run.kernel_calls.get("fused_encode", []):
        c["count"] = int(jax.device_get(c["count"]))
    driver.release(run)
    return run


def limits_of(root, cell):
    return load_json(os.path.join(root, "bench", "limits", cell["name"] + ".json"))


def readings_of(run, gap: float, n_tokens: int):
    """The numbers compared with the cell's limits, by the limits' names."""
    return {
        "logit_gap": gap,
        "greedy_tokens": n_tokens,
        "rollbacks_checked": run.rb[0],
        "rollback_mismatch": run.rb[1],
        "images_checked": run.im[0],
        "image_mismatch": run.im[1],
        "dump_failures": sum(h["dump_failures"] for h in run.health) + run.values.get("dump_failed", 0),
    }


def judge(limits, readings):
    """Each reading against its limit: (checks for the result line, correct)."""
    checks, correct = {}, True
    for name, lim in limits["checks"].items():
        v = readings[name]
        if "max" in lim:
            ok = v == v and v <= lim["max"]
            checks[name] = {"value": v, "limit": lim["max"], "is": "max"}
        else:
            ok = v >= lim["min"]
            checks[name] = {"value": v, "limit": lim["min"], "is": "min"}
        correct &= bool(ok)
    return checks, correct


def run_cell(root, manifest, cell, config, traffic, args, devices, t_proc, *, control=False) -> int:
    """One run of the cell, its result line last on stdout.  ``control``
    puts the float8 control in the program's place for the logit gap: the
    run must then come out not correct (``bench/control.py``, tests)."""
    from bench import trace as btrace

    cache, counter = prepare(root)
    kind = devices[0].device_kind
    log(f"cell {cell['name']}: config {cell['config']}, traffic {cell['traffic']}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}; device {kind} x {len(devices)}; compile cache {cache}")
    run = measure(root, manifest, cell, config, traffic, args.seed, args.seconds, args.trace,
                  devices, counter, t_proc)
    gap, n_tokens = reference_check(run, control=control) if run.served else (float("nan"), 0)
    health, rb, im, in_window, peak = run.health, run.rb, run.im, run.in_window, run.peak

    # ---- readings behind the metrics
    samples = run.rec.samples
    log(f"window {run.window_s:.3f} s; traced {run.trace}")
    log("samples: " + ", ".join(f"{k} {len(v)}" for k, v in sorted(samples.items())))
    log(f"compiles inside the window: {in_window['compiles']} backend compiles, "
        f"{in_window['traces']} traces; programs: {in_window['programs']}")
    log("kernel calls in the window: " + ", ".join(
        f"{k} {len(v)}" for k, v in sorted(run.kernel_calls.items())))
    log(f"rollbacks: fast {run.rec.counts.get('rollbacks_fast', 0)}, "
        f"slow {run.rec.counts.get('rollbacks_slow', 0)}")
    log("values: " + json.dumps({k: (v if not isinstance(v, list) else len(v))
                                 for k, v in run.values.items()}))
    for i, h in enumerate(health):
        log(f"DeltaCR.health()[{i}]: " + json.dumps(h, default=str))
    if rb[2] or im[2]:
        log("state differences: " + "; ".join(rb[2] + im[2]))

    # ---- metrics
    metrics = {}
    for m in metrics_of(manifest, cell["name"], run.trace):
        mod = load_module(os.path.join(root, "bench", "metrics", m["name"] + ".py"))
        v = mod.read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # ---- correctness
    checks, correct = judge(limits_of(root, cell), readings_of(run, gap, n_tokens))

    result = {
        "correct": correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(peak),
        },
    }
    if run.trace and run.red is not None:
        result["device"]["busy_s"] = run.red["busy_s"]
        result["device"]["window_s"] = run.red["window_s"]
        result["breakdown"] = {
            "device_ops": btrace.top(run.red["op_s"]),
            "idle_gaps": btrace.top(run.red["idle_gaps"]),
        }
    result["checks"] = checks
    for name, c in checks.items():
        bound = "<=" if c["is"] == "max" else ">="
        log(f"check {name}: {c['value']} (limit {bound} {c['limit']})")
    log(f"correct: {correct}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
